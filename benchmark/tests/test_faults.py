"""The check catches a broken timed path, and passes a sound one.

Each cell runs at a small size on the CPU through the harness's whole
run (world, warm launch, window, the check), without its look for a
chip: sound, under the control, and under each fault the cell can have
(benchmark/faults.py)."""

import numpy as np
import pytest

import faults
import run

BENCH = run.load_json("BENCHMARK.json")
SMALL = {
    "phold-16k.uniform": {"num_hosts": 1024, "pool_capacity": 8192},
    "relaychain-10k.bulk1m": {"num_circuits": 20},
}
SEED = 2**31 + 11
# The check that each broken path has to fail, for each reference.  At
# these sizes the limits set for the cells' own sizes do not hold for
# dest_skew and rate_off, so those faults are judged by the reading
# against the sound run's.
CAUGHT_BY = {
    "phold": {"control": "lost", "unchanged": "clock", "half": "late",
              "altered": "ledger", "slow": "rate_off", "local": "dest_skew",
              "self": "self_sends", "short_latency": "rate_off"},
    "onion": {"control": "short", "unchanged": "clock", "half": "undone",
              "altered": "short", "slow": "slowest_ms"},
}
RELATIVE = {"rate_off", "dest_skew"}


def _ref(cell):
    return run.resolve(BENCH, cell)["config"]["reference"]


def _cases():
    for w in BENCH["workloads"]:
        ref = _ref(w["name"])
        for kind in faults.APPLIES[ref]:
            yield pytest.param(w["name"], kind, CAUGHT_BY[ref][kind],
                               id=f"{w['name']}-{kind}")


def _run(cell, plant=None, program_kw=None):
    res, lines = run.run_cell(BENCH, cell, SEED, 2.0, require_chip=False,
                              overrides=SMALL[cell], plant=plant,
                              program_kw=program_kw)
    assert len(lines) == len(res["checks"])
    assert list(res)[-1] == "checks"
    return res


_SOUND = {}


def _sound(cell):
    if cell not in _SOUND:
        _SOUND[cell] = _run(cell)
    return _SOUND[cell]


@pytest.mark.parametrize("cell", list(SMALL))
def test_sound_run_is_correct(cell):
    res = _sound(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["sim_s_per_s"]["value"] > 0


@pytest.mark.parametrize("cell,kind,check", list(_cases()))
def test_broken_path_is_not_correct(cell, kind, check):
    fn, kw_fn, ctx = faults.plant(kind, _ref(cell))
    with ctx:
        res = _run(cell, fn, kw_fn)
    c = res["checks"][check]
    if check in RELATIVE:
        # A small world reads noisily: the fault has to read several
        # times what the sound run does.
        assert c["value"] > 4 * _sound(cell)["checks"][check]["value"] + 1
    else:
        assert not res["correct"]
        assert c["value"] > c["limit"], res["checks"]


def test_plain_phold_runs_its_rate_from_the_seed():
    from reference import phold
    a = phold.plain_received(512, 4, 10e6, 10e6, 0.5e9, 2**31 + 3)
    b = phold.plain_received(512, 4, 10e6, 10e6, 0.5e9, 2**31 + 3)
    c = phold.plain_received(512, 4, 10e6, 10e6, 0.5e9, 5)
    assert a == b and a != c
    # each host receives about 118 messages a simulated second here
    assert abs(a / 512 / 0.5 / 118 - 1) < 0.05
    slow = phold.plain_received(512, 4, 10e6, 15e6, 0.5e9, 2**31 + 3)
    assert slow < 0.8 * a


def test_dest_skew_reads_uniform_low_and_local_high():
    from reference import phold
    rng = np.random.default_rng(1)
    n, m = 16384, 20000
    src = rng.integers(0, n, m)
    dst = (src + 1 + rng.integers(0, n - 1, m)) % n
    assert phold.dest_skew(src, dst, n) < 5
    near = (src + 1 + rng.integers(0, n // 64 - 1, m)) % n
    assert phold.dest_skew(src, near, n) > 100
