"""Streaming parse-pipeline equivalence tests.

The chunk-local columnar encode (ingest/chunk.py) must be invisible to
semantics: native vs Python tokenizer and serial vs byte-range-parallel
all produce bit-identical Frames — values, NA positions, enum domains
and code order, time columns — on a fixture with quoted fields, NA
sentinels, and rows straddling range boundaries (the reference's
ParserTest equivalence discipline for MultiFileParseTask chunking).
"""
import importlib

import numpy as np
import pytest

import h2o3_tpu as h2o

# the package re-exports the parse() FUNCTION under the same attribute
# name as the module — resolve the module explicitly for monkeypatching
parse_mod = importlib.import_module("h2o3_tpu.ingest.parse")
from h2o3_tpu.ingest.parse import _is_int, parse, parse_setup


def _mixed_csv(nrow=200, quotes=True):
    """Mixed-type fixture: int, real, enum, time, plus NA sentinels in
    every column and (optionally) quoted fields with embedded commas."""
    rng = np.random.default_rng(7)
    lines = ["id,score,city,seen,note"]
    cities = ["ames", "berlin", "cairo", "delhi,town" if quotes else "delhitown"]
    for i in range(nrow):
        idv = "NA" if i % 31 == 7 else str(i + 1)
        score = "NaN" if i % 17 == 3 else f"{rng.normal():.6f}"
        c = cities[int(rng.integers(0, len(cities)))]
        city = f'"{c}"' if (quotes and "," in c) else c
        seen = "" if i % 23 == 5 else f"2021-{1 + i % 12:02d}-{1 + i % 28:02d}"
        note = f"n{i % 5}"
        lines.append(f"{idv},{score},{city},{seen},{note}")
    return "\n".join(lines) + "\n"


def _frames_equal(a, b):
    assert a.names == b.names
    assert a.nrow == b.nrow
    for n in a.names:
        va, vb = a.vec(n), b.vec(n)
        assert va.type == vb.type, n
        assert va.domain == vb.domain, n
        xa, xb = va.to_numpy(), vb.to_numpy()
        if xa.dtype.kind == "f":
            np.testing.assert_array_equal(np.isnan(xa), np.isnan(xb), err_msg=n)
            np.testing.assert_array_equal(xa[~np.isnan(xa)], xb[~np.isnan(xb)],
                                          err_msg=n)
        else:
            np.testing.assert_array_equal(xa, xb, err_msg=n)


@pytest.fixture
def mixed_file(tmp_path):
    p = tmp_path / "mixed.csv"
    p.write_text(_mixed_csv())
    return str(p)


@pytest.fixture
def unquoted_file(tmp_path):
    # no quotes: the native tokenizer accepts it (quoted files route to
    # the Python tokenizer), so this fixture exercises the native path
    p = tmp_path / "plain.csv"
    p.write_text(_mixed_csv(quotes=False))
    return str(p)


def test_native_vs_python_tokenizer_identical(unquoted_file, monkeypatch):
    setup = parse_setup(unquoted_file)
    fr_native = parse([unquoted_file], setup)
    if not parse_mod.LAST_PROFILE.get("native"):
        pytest.skip("native tokenizer unavailable in this image")
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([unquoted_file], setup)
    assert not parse_mod.LAST_PROFILE["native"]
    _frames_equal(fr_native, fr_python)


def test_serial_vs_parallel_identical(mixed_file, monkeypatch):
    setup = parse_setup(mixed_file)
    fr_serial = parse([mixed_file], setup)
    assert parse_mod.LAST_PROFILE["chunks"] == 1
    # force the byte-range fan-out: every file goes parallel, and rows
    # straddle the newline-aligned range boundaries
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr_par = parse([mixed_file], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    _frames_equal(fr_serial, fr_par)


def test_parallel_python_fallback_identical(mixed_file, monkeypatch):
    setup = parse_setup(mixed_file)
    fr_serial = parse([mixed_file], setup)
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_par = parse([mixed_file], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    assert not parse_mod.LAST_PROFILE["native"]
    _frames_equal(fr_serial, fr_par)


def test_quoted_fields_and_na_sentinels(mixed_file):
    fr = parse([mixed_file], parse_setup(mixed_file))
    city = fr.vec("city")
    assert city.type == "enum"
    assert "delhi,town" in city.domain          # quoted comma survives
    assert fr.vec("id").na_count() == sum(1 for i in range(200) if i % 31 == 7)
    assert fr.vec("seen").type == "time"
    assert fr.vec("seen").na_count() == sum(1 for i in range(200) if i % 23 == 5)


def test_numeric_na_sentinel_routes_off_native(tmp_path):
    # a numeric na_string ('-999') cannot be expressed in the native
    # numeric fast path (any non-numeric token is already NaN there) —
    # the parse must fall back and still honor the sentinel
    p = tmp_path / "sentinel.csv"
    p.write_text("a,b\n1,-999\n-999,2\n3,4\n")
    fr = h2o.import_file(str(p), na_strings=["-999"])
    a, b = fr.vec("a").to_numpy(), fr.vec("b").to_numpy()
    assert np.isnan(a[1]) and np.isnan(b[0])
    assert a[0] == 1 and b[2] == 4


# ---------------- satellite: lexical int detection / wide ints ----------


def test_is_int_lexical():
    assert _is_int("12") and _is_int("-3") and _is_int(" +7 ")
    assert not _is_int("1.5") and not _is_int("1e5") and not _is_int("x2")
    # the float-round-trip misclassifies this as int AND munges it;
    # lexical detection keeps it int and exact
    assert _is_int("9007199254740993")


@pytest.mark.parametrize("force_python", [False, True])
def test_wide_int_exact_roundtrip(tmp_path, monkeypatch, force_python):
    wide = (1 << 53) + 1          # not representable in float64
    p = tmp_path / "wide.csv"
    p.write_text("k,v\n%d,1\n%d,2\n%d,3\n" % (wide, wide + 2, -wide))
    if force_python:
        monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr = parse([str(p)], parse_setup(str(p)))
    k = fr.vec("k").to_numpy()
    assert k.dtype == np.int64
    assert list(k) == [wide, wide + 2, -wide]


def test_wide_int_with_na_degrades_to_real(tmp_path):
    wide = (1 << 53) + 1
    p = tmp_path / "widena.csv"
    p.write_text("k\n%d\nNA\n7\n" % wide)
    fr = parse([str(p)], parse_setup(str(p)))
    k = fr.vec("k").to_numpy()
    assert np.isnan(k[1]) and k[2] == 7  # NA kept; no silent munge claim


# ---------------- satellite: _rbind enum domain union -------------------


def test_rbind_enum_union_remaps_codes(tmp_path):
    (tmp_path / "a.csv").write_text("g,x\nred,1\nblue,2\nred,3\n")
    (tmp_path / "b.csv").write_text("g,x\ngreen,4\nred,5\nNA,6\n")
    fr = h2o.import_file([str(tmp_path / "a.csv"), str(tmp_path / "b.csv")])
    g = fr.vec("g")
    assert g.type == "enum"
    assert g.domain == ("blue", "green", "red")
    codes = g.to_numpy()
    labels = [None if c < 0 else g.domain[c] for c in codes]
    assert labels == ["red", "blue", "red", "green", "red", None]
    np.testing.assert_allclose(fr.vec("x").to_numpy(), [1, 2, 3, 4, 5, 6])


def test_rbind_wide_int_stays_exact(tmp_path):
    wide = (1 << 53) + 1
    (tmp_path / "a.csv").write_text("k\n%d\n%d\n" % (wide, wide + 2))
    (tmp_path / "b.csv").write_text("k\n5\n6\n")
    fr = h2o.import_file([str(tmp_path / "a.csv"), str(tmp_path / "b.csv")])
    k = fr.vec("k").to_numpy()
    # float64 concat promotion would munge wide ints; the merge must
    # keep the exact int64 representation across the two files
    assert k.dtype == np.int64
    assert list(k) == [wide, wide + 2, 5, 6]


def test_all_na_numeric_column(tmp_path):
    import warnings
    p = tmp_path / "allna.csv"
    p.write_text("a,b\nNA,1\nNA,2\nNA,3\n")
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # any RuntimeWarning fails
        fr = parse([str(p)], parse_setup(str(p)))
    assert fr.vec("a").na_count() == 3


def test_formerly_divergent_tokens_stay_native(tmp_path, monkeypatch):
    # the three documented decline classes of the pre-ISSUE-14 native
    # tokenizer — quoted fields, >63-char numerics, unicode whitespace —
    # now parse NATIVELY (no fallback at all), with the same values the
    # Python tokenizer produces
    long_num = "0." + "1" * 70
    rows = [f"{i},plain" for i in range(2, 400)]
    body = [f"{long_num},first"] + rows + ['9,"quoted,tail"']
    p = tmp_path / "mix.csv"
    p.write_text("x,s\n" + "\n".join(body) + "\n")
    setup = parse_setup(str(p))
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    assert parse_mod.LAST_PROFILE["native"]
    assert parse_mod.LAST_PROFILE["fallback_ranges"] == 0
    x = fr.vec("x").to_numpy()
    assert x[0] == pytest.approx(float(long_num))   # not munged to NA
    assert "quoted,tail" in fr.vec("s").domain


# ---------------- tentpole: native-vs-Python tokenizer parity matrix ----
#
# The range-scoped fallback MIXES tokenizers across byte ranges of one
# column, so the native tokenizer must bit-match the Python one on every
# accepted token class — each case asserts (1) the native path handled
# the file (no fallback), (2) the frame is bit-identical to the pure
# Python tokenizer's.

PARITY_CASES = {
    "quoted_embedded_delimiter":
        'g,x\n"a,b",1\nplain,2\n"c,d,e",3\n"a,b",4\n',
    "quoted_embedded_newline":
        'g,x\n"line1\nline2",1\nplain,2\n"a\nb\nc",3\n',
    "escaped_quotes":
        'g,x\n"he said ""hi""",1\n"""lead",2\n"trail""",3\nplain,4\n',
    "long_numerics":
        "x,y\n" + "0." + "1" * 70 + ",1\n" + "9" * 80 + "e-70,2\n3,3\n",
    "unicode_whitespace":
        "g,x\n padded ,1\n　wide　,2\n ascii , 3 \n",
    "na_inside_quotes":
        'g,x\n"NA",1\n"na",2\nreal,3\n"",4\n',
    "crlf_lf_mixed":
        "g,x\r\na,1\r\nb,2\nc,3\r\nd,4\n",
    "quoted_numeric_cells":
        'x,y\n"1.5",1\n"2e3",2\n" 7 ",3\n',
}


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_tokenizer_parity_matrix(tmp_path, monkeypatch, case):
    p = tmp_path / f"{case}.csv"
    p.write_bytes(PARITY_CASES[case].encode("utf-8"))
    setup = parse_setup(str(p))
    fr_native = parse([str(p)], setup)
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    # the native path itself handled every range — no silent fallback
    assert parse_mod.LAST_PROFILE["native"], \
        parse_mod.LAST_PROFILE["fallback_reasons"]
    assert parse_mod.LAST_PROFILE["fallback_ranges"] == 0
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([str(p)], setup)
    assert not parse_mod.LAST_PROFILE["native"]
    _frames_equal(fr_native, fr_python)


def test_parity_matrix_parallel_ranges(tmp_path, monkeypatch):
    # the same token classes crossing byte-range boundaries: quoted
    # fields with embedded newlines must not be split mid-field by the
    # range scan (csv_chunk_bounds quote-parity alignment)
    rng = np.random.default_rng(3)
    lines = ["g,x"]
    for i in range(400):
        kind = i % 5
        if kind == 0:
            lines.append(f'"a,{i}\nb",{i}')
        elif kind == 1:
            lines.append(f'"q""{i}""",{i}')
        elif kind == 2:
            lines.append(f" pad{i % 7} ,{i}")
        elif kind == 3:
            lines.append('"NA",%d' % i)
        else:
            lines.append(f"plain{i % 11},{i}")
    p = tmp_path / "matrix.csv"
    p.write_bytes(("\n".join(lines) + "\n").encode("utf-8"))
    setup = parse_setup(str(p))
    fr_serial = parse([str(p)], setup)
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr_par = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    assert parse_mod.LAST_PROFILE["native"]
    assert parse_mod.LAST_PROFILE["fallback_ranges"] == 0
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([str(p)], setup)
    _frames_equal(fr_serial, fr_par)
    _frames_equal(fr_par, fr_python)


def test_fallback_is_range_scoped(tmp_path, monkeypatch):
    # ONE poisoned range (a ragged row the native scan declines) must
    # not re-parse its neighbors: every other range stays native, the
    # fallback is counted with its reason, and the frame still matches
    # the pure-Python parse
    lines = [f"{i},tok{i % 13}" for i in range(1, 800)]
    lines[500] = "9,extra,cells,beyond,the,schema"   # ragged → decline
    p = tmp_path / "poison.csv"
    p.write_text("x,s\n" + "\n".join(lines) + "\n")
    setup = parse_setup(str(p))
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr = parse([str(p)], setup)
    prof = dict(parse_mod.LAST_PROFILE)
    assert prof["chunks"] > 2
    assert prof["fallback_ranges"] >= 1          # the poisoned range
    assert prof["native_ranges"] == prof["chunks"] - prof["fallback_ranges"]
    assert prof["native_ranges"] >= prof["chunks"] - 2   # neighbors survive
    assert "ragged_rows" in prof["fallback_reasons"]
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([str(p)], setup)
    _frames_equal(fr, fr_python)


def test_streamed_chunks_survive_range_fallback(tmp_path, monkeypatch):
    # the wasted-work seam: when a range declines mid-stream, the other
    # ranges' already-streamed device chunks survive — nothing lands in
    # the h2o3_ingest_h2d_bytes_discarded_total counter and the
    # streamed assembly covers every chunk (fallback chunks add late)
    from h2o3_tpu import telemetry
    lines = [f"{i},{i * 0.5}" for i in range(1, 800)]
    lines[400] = "9,1,overflow"                      # ragged → decline
    p = tmp_path / "poison2.csv"
    p.write_text("a,b\n" + "\n".join(lines) + "\n")
    setup = parse_setup(str(p))
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    telemetry.install()
    before = telemetry.registry().value(
        "h2o3_ingest_h2d_bytes_discarded_total")
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    monkeypatch.setenv("H2O3_INGEST_STREAM", "1")
    fr = parse([str(p)], setup)
    prof = dict(parse_mod.LAST_PROFILE)
    assert prof["streamed"] and prof["fallback_ranges"] >= 1
    assert telemetry.registry().value(
        "h2o3_ingest_h2d_bytes_discarded_total") == before
    a = fr.vec("a").to_numpy()
    assert fr.nrow == 799
    assert a[0] == 1 and a[798] == 799


def test_underscore_numerics_parity(tmp_path, monkeypatch):
    # PEP-515 grouped numerics: float("1_000") == 1000.0 — the native
    # tokenizer must agree, or a range-scoped fallback would read the
    # same token as NA in native ranges and 1000.0 in Python ones
    p = tmp_path / "grouped.csv"
    p.write_text("x,s\n1_000,a\n2_5.5,b\n1_0e1_0,c\n_1,d\n1_,e\n1__0,f\n")
    # invalid groupings would poison the sample-based type guess into
    # enum; the parity under test is the NUMERIC encode of these tokens
    setup = parse_setup(str(p), header=True,
                        column_types=["real", "enum"])
    fr_native = parse([str(p)], setup)
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    assert parse_mod.LAST_PROFILE["native"]
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([str(p)], setup)
    _frames_equal(fr_native, fr_python)
    x = fr_native.vec("x").to_numpy()
    assert x[0] == 1000.0 and x[1] == 25.5 and x[2] == 1e11
    assert np.isnan(x[3]) and np.isnan(x[4]) and np.isnan(x[5])


def test_late_quote_beyond_probe_window_retries(tmp_path, monkeypatch):
    # a file whose FIRST quote (a quoted field with embedded newlines)
    # sits past the probe window: the naive newline boundaries would
    # split it mid-quote — parse must detect the late quote on decline
    # and retry with exact quote-aware boundaries, ending bit-identical
    # to the pure-Python whole-file parse, all ranges native
    monkeypatch.setattr(parse_mod, "_QUOTE_PROBE_BYTES", 256)
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    lines = ["g,x"] + [f"plain{i % 7},{i}" for i in range(60)]
    lines.append('"multi\nline\nfield",999')       # beyond byte 256
    lines += [f"tail{i % 5},{i}" for i in range(40)]
    p = tmp_path / "latequote.csv"
    p.write_text("\n".join(lines) + "\n")
    setup = parse_setup(str(p))
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    fr = parse([str(p)], setup)
    prof = dict(parse_mod.LAST_PROFILE)
    assert prof["chunks"] > 1
    assert prof["native"] and prof["fallback_ranges"] == 0
    assert "multi\nline\nfield" in fr.vec("g").domain
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1 << 30)
    fr_python = parse([str(p)], setup)
    _frames_equal(fr, fr_python)


def test_quoted_file_without_toolchain_stays_serial(tmp_path, monkeypatch):
    # no native toolchain + a quoted file: there is no state machine to
    # place quote-safe boundaries, so the file must parse as ONE range
    # (serial, quote-correct csv.reader) — blind newline cuts would
    # split the quoted-newline field and corrupt rows silently
    import h2o3_tpu.native as native_mod
    lines = ["g,x"] + [f"p{i % 3},{i}" for i in range(50)]
    lines.append('"multi\nline\nfield",999')
    lines += [f"q{i % 3},{i}" for i in range(50)]
    p = tmp_path / "noolchain.csv"
    p.write_text("\n".join(lines) + "\n")
    setup = parse_setup(str(p))
    fr_ref = parse([str(p)], setup)              # whole-file reference
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    monkeypatch.setattr(native_mod, "chunk_bounds",
                        lambda *a, **k: None)
    fr = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] == 1
    assert "multi\nline\nfield" in fr.vec("g").domain
    _frames_equal(fr_ref, fr)


def test_ingest_workers_override(monkeypatch):
    monkeypatch.setenv("H2O3_INGEST_WORKERS", "3")
    assert parse_mod.ingest_workers() == 3
    monkeypatch.setenv("H2O3_INGEST_WORKERS", "not-a-number")
    assert parse_mod.ingest_workers() >= 1       # falls back to cpu count
    monkeypatch.delenv("H2O3_INGEST_WORKERS")
    import os as _os
    assert parse_mod.ingest_workers() == max(1, _os.cpu_count() or 4)


def test_rbind_time_stays_time(tmp_path):
    (tmp_path / "a.csv").write_text("t\n2020-01-01\n2020-01-02\n")
    (tmp_path / "b.csv").write_text("t\n2021-05-05\nNA\n")
    fr = h2o.import_file([str(tmp_path / "a.csv"), str(tmp_path / "b.csv")])
    t = fr.vec("t")
    assert t.type == "time"
    ms = t.to_numpy()
    assert ms[0] == np.datetime64("2020-01-01", "ms").astype(np.int64)
    assert ms[3] == t.TIME_NA
    assert fr.vec("t").na_count() == 1


# ---------------- satellite: rollup kernel recompile --------------------


def test_rollup_no_recompile_across_nrow():
    from h2o3_tpu.frame.rollups import _rollup_kernel
    from h2o3_tpu.parallel.mesh import padded_len

    n1, n2 = 90, 100
    assert padded_len(n1) == padded_len(n2)  # same padding bucket
    v1 = h2o.Vec.from_numpy(np.arange(n1, dtype=np.float32))
    v2 = h2o.Vec.from_numpy(np.arange(n2, dtype=np.float32) * 2)
    r1 = v1.rollups()
    before = _rollup_kernel._cache_size()
    r2 = v2.rollups()
    # nrow is traced, shape unchanged — the second length must HIT
    assert _rollup_kernel._cache_size() == before
    assert r1["rows"] == n1 and r2["rows"] == n2
    assert r1["mean"] == pytest.approx((n1 - 1) / 2)
    assert r2["max"] == pytest.approx(2 * (n2 - 1))


# ---------------- ISSUE 16: nogil enum encode / compressed / multihost --


def test_enum_encode_parity_matrix(tmp_path, monkeypatch):
    # the nogil native enum encode must bit-match the Python encode on
    # its hard cases IN ONE FILE: NA labels, duplicate labels recurring
    # across byte ranges (domain-union code remap), >64KiB labels
    # (arena slab growth), and quoted cells straddling range boundaries
    big_a = "L" * (70 * 1024)
    big_b = "M" * (66 * 1024) + ",tail"          # >64KiB AND quoted
    labels = ["alpha", "beta", "NA", '"q,uoted"']
    lines = ["g,x"]
    for i in range(600):
        if i == 3:
            lab = big_a
        elif i == 590:
            lab = f'"{big_b}"'
        else:
            lab = labels[i % len(labels)]
        lines.append(f"{lab},{i}")
    p = tmp_path / "enum.csv"
    p.write_bytes(("\n".join(lines) + "\n").encode("utf-8"))
    setup = parse_setup(str(p))
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr_native = parse([str(p)], setup)
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    assert parse_mod.LAST_PROFILE["native"], \
        parse_mod.LAST_PROFILE["fallback_reasons"]
    assert parse_mod.LAST_PROFILE["fallback_ranges"] == 0
    g = fr_native.vec("g")
    assert big_a in g.domain and big_b in g.domain
    assert g.na_count() > 0                      # NA labels stayed NA
    monkeypatch.setattr(parse_mod, "_native_available", lambda: False)
    fr_python = parse([str(p)], setup)
    assert not parse_mod.LAST_PROFILE["native"]
    _frames_equal(fr_native, fr_python)


@pytest.mark.parametrize("fmt", ["gzip", "zstd"])
def test_compressed_member_parallel_bit_equal(tmp_path, monkeypatch, fmt):
    # member/frame-parallel compressed ingest: multi-member gzip and
    # multi-frame zstd inflate through the index plan, range-parse the
    # decompressed buffer, and come out bit-identical to the plain file
    # with ZERO whole-import fallbacks
    from h2o3_tpu.ingest.compress import (gzip_compress_members,
                                          zstd_compress_store)
    csv = _mixed_csv()
    plain = tmp_path / "plain.csv"
    plain.write_text(csv)
    fr_plain = parse([str(plain)], parse_setup(str(plain)))
    if fmt == "gzip":
        cp = tmp_path / "data.csv.gz"
        cp.write_bytes(gzip_compress_members(csv.encode(), member_bytes=1024))
    else:
        cp = tmp_path / "data.csv.zst"
        cp.write_bytes(zstd_compress_store(csv.encode(), frame_bytes=1024))
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    fr_c = parse([str(cp)], parse_setup(str(cp)))
    comp = parse_mod.LAST_PROFILE["compressed"][0]
    assert comp["format"] == fmt
    assert comp["members"] > 1 and comp["parallel"]
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    if parse_mod._native_available():
        assert parse_mod.LAST_PROFILE["fallback_ranges"] == 0
    _frames_equal(fr_plain, fr_c)


def test_gzip_single_stream_degrades_counted(tmp_path):
    # a single-member gzip can't member-parallelize: ingest degrades to
    # one serial inflate, counts the reason, and still parses correctly
    import gzip as _gz

    from h2o3_tpu import telemetry
    csv = _mixed_csv(nrow=80)
    cp = tmp_path / "single.csv.gz"
    cp.write_bytes(_gz.compress(csv.encode(), 6, mtime=0))
    c0 = telemetry.registry().value(
        "h2o3_ingest_fallback_total", {"reason": "gzip_single_stream"})
    fr = parse([str(cp)], parse_setup(str(cp)))
    comp = parse_mod.LAST_PROFILE["compressed"][0]
    assert comp["members"] == 1 and not comp["parallel"]
    assert comp["reason"] == "gzip_single_stream"
    assert telemetry.registry().value(
        "h2o3_ingest_fallback_total",
        {"reason": "gzip_single_stream"}) == c0 + 1
    assert fr.nrow == 80
    plain = tmp_path / "single.csv"
    plain.write_text(csv)
    _frames_equal(parse([str(plain)], parse_setup(str(plain))), fr)


def test_multihost_shard_local_parse_parity(tmp_path, monkeypatch):
    # multi-host shard-local parse, simulated on the single-process
    # mesh via the _proc_conf seam: each "process" tokenizes ONLY the
    # byte ranges whose rows land in its shards, the per-process H2D
    # counter sees only the local block, and the stitched row spans are
    # bit-identical to the single-process parse
    from h2o3_tpu import telemetry
    rng = np.random.default_rng(5)
    lines = ["x,y,z"]
    for i in range(800):
        x = "NA" if i % 97 == 13 else f"{rng.normal():.6f}"
        lines.append(f"{x},{i},{i * 0.25}")
    p = tmp_path / "mh.csv"
    p.write_text("\n".join(lines) + "\n")
    setup = parse_setup(str(p))
    if not parse_mod._native_available():
        pytest.skip("native tokenizer unavailable in this image")
    fr_single = parse([str(p)], setup)
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1)
    frames, profs = [], []
    for pidx in range(2):
        monkeypatch.setattr(parse_mod, "_proc_conf",
                            lambda pidx=pidx: (2, pidx))
        h0 = telemetry.registry().value(
            "h2o3_h2d_pipeline_bytes_total", {"pipeline": "ingest"})
        fr = parse([str(p)], setup)
        h1 = telemetry.registry().value(
            "h2o3_h2d_pipeline_bytes_total", {"pipeline": "ingest"})
        prof = parse_mod.LAST_PROFILE["multihost"]
        assert prof is not None, parse_mod.LAST_PROFILE["fallback_reasons"]
        assert prof["nproc"] == 2 and prof["pidx"] == pidx
        assert prof["rows_total"] == 800
        # shard-local: this process tokenized a strict subset of ranges
        assert 0 < prof["ranges_local"] < prof["ranges_total"]
        # per-process H2D attribution: exactly the local block's bytes
        assert h1 - h0 == prof["h2d_bytes"]
        frames.append(fr)
        profs.append(prof)
    # the two spans are disjoint, contiguous, and start at row 0
    s0, s1 = profs[0]["row_span"], profs[1]["row_span"]
    assert s0[0] == 0 and s0[1] == s1[0]
    assert s1[1] >= 800                          # padded tail included
    for n in fr_single.names:
        ref = fr_single.vec(n).to_numpy()
        for fr, (lo, hi) in zip(frames, (s0, s1)):
            hi = min(hi, fr_single.nrow)
            got = fr.vec(n).to_numpy()[lo:hi]
            want = ref[lo:hi]
            if got.dtype.kind == "f":
                np.testing.assert_array_equal(
                    np.isnan(got), np.isnan(want), err_msg=n)
                np.testing.assert_array_equal(
                    got[~np.isnan(got)], want[~np.isnan(want)], err_msg=n)
            else:
                np.testing.assert_array_equal(got, want, err_msg=n)


# ---------------- satellite: enum device streaming (ISSUE 17) ----------


def _region_enum_csv(nrow=6000):
    """Enum column whose domain depends on the row REGION: each third of
    the file sees a different city pair, so parallel byte-range chunks
    encode DIFFERENT chunk-local code spaces and the streamed device
    assembly must remap every chunk through its per-chunk LUT section
    (chunk-local code 0 decodes to a different label per region)."""
    rng = np.random.default_rng(11)
    regions = [("ames", "berlin"), ("cairo", "delhi"), ("essen", "fargo")]
    lines = ["id,e,x"]
    for i in range(nrow):
        pair = regions[min(i * len(regions) // nrow, len(regions) - 1)]
        e = "" if i % 97 == 13 else pair[int(rng.integers(0, 2))]
        lines.append(f"{i},{e},{rng.normal():.5f}")
    return "\n".join(lines) + "\n"


def test_enum_streamed_device_parity(tmp_path, monkeypatch):
    """Enum codes ride the worker-side prepack + per-chunk streamed H2D
    path and the device-remapped union codes are bit-identical to the
    serial host-merge parse — values, NA positions, domain order."""
    p = tmp_path / "region.csv"
    p.write_text(_region_enum_csv())
    setup = parse_setup(str(p))
    fr_serial = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] == 1
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1 << 12)
    monkeypatch.setenv("H2O3_INGEST_STREAM", "1")
    fr_par = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    assert parse_mod.LAST_PROFILE["streamed"]
    assert fr_par.vec("e").domain == ("ames", "berlin", "cairo", "delhi",
                                      "essen", "fargo")
    _frames_equal(fr_serial, fr_par)


def test_enum_stream_cardinality_blowout_falls_back(tmp_path, monkeypatch):
    """A union past MAX_ENUM_CARDINALITY demotes the column out of the
    streamed set (the host merge takes over, exactly the pre-streaming
    semantics) — parity with the serial parse survives the demotion."""
    import h2o3_tpu.ingest.chunk as chunk_mod
    lines = ["id,e"]
    for i in range(4000):
        lines.append(f"{i},lab{i % 600:04d}")
    p = tmp_path / "blow.csv"
    p.write_text("\n".join(lines) + "\n")
    monkeypatch.setattr(chunk_mod, "MAX_ENUM_CARDINALITY", 128)
    setup = parse_setup(str(p))
    fr_serial = parse([str(p)], setup)
    monkeypatch.setattr(parse_mod, "_PARALLEL_PARSE_BYTES", 1 << 12)
    monkeypatch.setenv("H2O3_INGEST_STREAM", "1")
    fr_par = parse([str(p)], setup)
    assert parse_mod.LAST_PROFILE["chunks"] > 1
    _frames_equal(fr_serial, fr_par)


def test_native_build_is_stamped_for_this_source_and_this_cpu(
        tmp_path, monkeypatch):
    """The tokenizer is built with -march=native and is not in git: its
    stamp covers the source AND the host CPU's flags, so a tree copied to
    another machine rebuilds instead of dying of SIGILL; a .so with no
    stamp, or an older source-only one, is stale."""
    import hashlib
    import shutil

    from h2o3_tpu import native
    if native.lib() is None:
        pytest.skip("native tokenizer unavailable in this image")
    so, stamp = tmp_path / "libfastcsv.so", tmp_path / "libfastcsv.so.srchash"
    shutil.copy(native._SO, so)
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_HASH", str(stamp))
    assert native._stale()                              # no stamp at all
    with open(native._SRC, "rb") as f:
        stamp.write_text(hashlib.sha256(f.read()).hexdigest())
    assert native._stale()                              # source-only stamp
    stamp.write_text(native._build_stamp())
    assert not native._stale()
    assert native._build() and not native._stale()      # rebuild re-stamps
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "libfastcsv.so", "libfastcsv.so.srchash"]       # no torn temporaries
