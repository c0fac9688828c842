"""warm_s (s): wall seconds of the host span `warm`, the first launch:
loading the executable from the compile cache, or compiling it, and
running it once (host clock)."""


def read(rec):
    return rec.get("spans", {}).get("warm")
