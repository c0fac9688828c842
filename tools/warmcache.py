"""Pre-compile the standard shape buckets into the persistent XLA cache.

Thin front end over shadow1_tpu.shapes.warm_buckets (the same entry
`shadow1-tpu warm` uses): builds one canonical world per (app flavor,
host bucket), pads it into its bucket, and AOT lowers + compiles
engine.run_until so the executable lands in the persistent compilation
cache (JAX_COMPILATION_CACHE_DIR where set, else `.jax_cache/` in the
checkout).  Later
processes tracing the same graph skip the backend compile entirely --
`profile.compiles` / `compile_ms` (trace.py, gated by tools/benchdiff.py)
make the win measurable.  See docs/shapes.md.

    python tools/warmcache.py                      # standard set
    python tools/warmcache.py --buckets 64 256     # specific rungs
    python tools/warmcache.py --apps phold         # one flavor
"""

from __future__ import annotations

import argparse
import json
import sys

import pathlib

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from shadow1_tpu import shapes  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AOT-compile the standard shape buckets into the "
                    "persistent XLA cache")
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    metavar="H",
                    help="host bucket sizes (default: "
                         f"{shapes.STANDARD_HOST_BUCKETS})")
    ap.add_argument("--apps", nargs="+", default=("phold", "bulk"),
                    choices=shapes.WARM_APPS,
                    help="world flavors (default: phold + bulk; "
                         "bulk-scope warms the --scope default config)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    log = None
    if not args.quiet:
        def log(rec):  # noqa: E306
            print(f"warm {rec['app']} @ {rec['bucket_hosts']} hosts: "
                  f"lower {rec['lower_s']}s, compile {rec['compile_s']}s",
                  file=sys.stderr)
    records = shapes.warm_buckets(buckets=args.buckets, apps=args.apps,
                                  log=log)
    print(json.dumps({"warmed": records}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
