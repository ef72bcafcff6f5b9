"""The benchmark's own code: everything that decides a number lives here."""
