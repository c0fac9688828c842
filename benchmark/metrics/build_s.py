"""build_s (s): wall seconds of the host span `build`, the world's
assembly by the program's builder and the benchmark's inputs (host clock)."""


def read(rec):
    return rec.get("spans", {}).get("build")
