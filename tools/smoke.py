"""Run the tier-0 smoke subset: one bitwise pin per subsystem, <5 min.

The full tier-1 sweep (`pytest tests/ -m 'not slow'`) takes ~40 minutes
on CI hardware -- far too slow for an edit-compile-check loop.  Almost
every regression that matters in this repo is a DETERMINISM break:
a change that perturbs the bitwise trajectory of a pinned world.  The
`tier0` marker (registered in tests/conftest.py) tags exactly one such
pin per subsystem:

  - engine       test_engine_phold.py  phold across window batching
  - tcp          test_tcp.py           bitwise-identical lossy bulk runs
  - netem        test_netem.py         neutral overlay block identity
  - parallel     test_parallel.py      8-device mesh vs single device
  - replay       test_replay.py        checkpoint replay verifies bitwise
  - megakernel   test_megakernel.py    fused vs reference trajectories
  - lineage      test_lineage.py       traced vs untraced trajectories
  - statescope   test_statescope.py    digest determinism, mesh digest
                                       identity, fault localization
  - server       test_server.py        serve round-trip: a submitted
                                       run matches direct sim.run
                                       bitwise, clean shutdown
  - servescope   test_servescope.py    a served request's
                                       request_metrics.json carries
                                       the solo run's rc and event
                                       count (observability is
                                       host-side only)
  - ensemble     test_ensemble.py      world k of a vmapped ensemble
                                       vs the same world run solo:
                                       bitwise leaf-for-leaf (phold
                                       rx_batch 1/2, lossy bulk TCP,
                                       per-world netem churn)
  - pipeline     test_pipeline.py      every drain artifact (flight,
                                       lineage, statescope) byte-
                                       identical sync vs pipelined
                                       window launches

(The continuous-batching pin -- two co-batched server requests each
bitwise their solo run, tests/test_batch.py -- needs ~3 min of solo
references plus a train and lives in tier-1 instead.)

Together they run in well under five minutes on the virtual 8-device
CPU mesh, giving a fast did-I-break-determinism signal before paying
for the full sweep.  A green tier-0 does NOT replace tier-1; it gates
whether tier-1 is worth starting.

Usage (from anywhere; the script pins cwd to the repo root):

    python tools/smoke.py            # run the subset
    python tools/smoke.py -x -q      # extra pytest args pass through

Exit code is pytest's exit code.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    env = dict(os.environ)
    # Tests run on the CPU; conftest.py enforces the same, but set it
    # here too so collection itself never reaches for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [
        sys.executable, "-m", "pytest", "tests/", "-q", "-m", "tier0",
        "-p", "no:cacheprovider", "-p", "no:randomly",
    ] + argv
    print("[smoke] " + " ".join(cmd), flush=True)
    return subprocess.call(cmd, cwd=REPO, env=env)


if __name__ == "__main__":
    sys.exit(main())
