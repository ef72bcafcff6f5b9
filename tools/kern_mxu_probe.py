"""Does a [M, K]x[K, N] Mosaic matmul with M << 128 cost the same as
M=128 (systolic-array row waste)? Times the bare hist-shaped contraction
at several M.  K=8192 (tile), N=896 (F*W).

``L=4`` chains L DEPENDENT contractions per fori step (each left
operand perturbed by the previous output, like the fused multi-level
window feeds nid forward) — per-contraction time vs L=1 shows whether
back-to-back MXU issue at the hist shape keeps the array busy, i.e.
how much of the multi-level win is dispatch/sync amortization vs
in-kernel pipelining. ``FW=448`` probes the W=16 packed geometry."""
import sys, os, time, functools
sys.path.insert(0, '/root/repo')

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = int(os.environ.get("ROWS", 2_500_608))
TILE = int(os.environ.get("TILE", 8192))
FW = int(os.environ.get("FW", 896))
REPS = int(os.environ.get("REPS", 40))
LCHAIN = max(1, int(os.environ.get("L", 1)))


def run(M):
    def kern(l_ref, r_ref, out_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc = jax.lax.dot_general(
            l_ref[...], r_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=(jnp.int32 if l_ref.dtype == jnp.int8
                                    else jnp.float32))
        acc_ref[...] += acc.astype(acc_ref.dtype)

        @pl.when(i == ROWS // TILE - 1)
        def _f():
            out_ref[...] = acc_ref[...]

    call = pl.pallas_call(
        kern,
        grid=(ROWS // TILE,),
        in_specs=[pl.BlockSpec((M, TILE), lambda r: (0, 0)),
                  pl.BlockSpec((FW, TILE), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((M, FW), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, FW), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M, FW), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 2 ** 20),
        interpret=os.environ.get("H2O3_PALLAS_INTERPRET", "") == "1",
    )
    rng = np.random.default_rng(0)
    DT = jnp.int8 if os.environ.get("DT") == "i8" else jnp.bfloat16
    if DT == jnp.int8:
        L = jnp.asarray(rng.integers(-127, 127, size=(M, TILE)).astype(np.int8))
        R = jnp.asarray(rng.integers(0, 2, size=(FW, TILE)).astype(np.int8))
    else:
        L = jnp.asarray(rng.normal(size=(M, TILE)).astype(np.float32)).astype(DT)
        R = jnp.asarray(rng.normal(size=(FW, TILE)).astype(np.float32)).astype(DT)

    @jax.jit
    def loop(L, R, s0):
        def body(i, carry):
            s, L = carry
            # LCHAIN dependent contractions back-to-back (the fused
            # multi-level window's issue pattern): each left operand
            # perturbed by the previous output so Mosaic can't CSE
            for _ in range(LCHAIN):
                out = call(L, R)
                L = (L + (out[0, 0] * 1e-20).astype(L.dtype)
                     if L.dtype != jnp.int8 else
                     L ^ (out[0, 0].astype(jnp.int32) % 2).astype(jnp.int8))
            return s + out[0, 0], L
        return jax.lax.fori_loop(0, REPS, body, (s0, L))

    out = loop(L, R, 0.0)
    _ = float(jax.device_get(out[0]))
    t0 = time.time()
    out2 = loop(L, R, 1e-7)
    _ = float(jax.device_get(out2[0]))
    dt = (time.time() - t0) / (REPS * LCHAIN)
    flops = 2 * M * FW * ROWS
    tag = f" L={LCHAIN}" if LCHAIN > 1 else ""
    print(f"M={M:4d}:{tag} {dt*1000:7.3f} ms/contraction  "
          f"({flops/dt/1e12:6.1f} TFLOP/s)", flush=True)


if __name__ == "__main__":
    for M in (map(int, sys.argv[1:]) if len(sys.argv) > 1
              else (6, 24, 96, 128, 256)):
        run(M)
