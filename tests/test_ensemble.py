"""Ensemble worlds: vmap whole simulations over a leading world axis.

docs/ensemble.md promises for the ensemble subsystem
(shadow1_tpu/ensemble, sim.run_ensemble, the drain world columns):

* Bitwise solo equivalence: world k of a stacked ensemble run is leaf-
  for-leaf bitwise identical to the same world run solo through
  engine.run_until on the same launch grid -- across arrival batching
  (rx_batch 1 and 2), lossy bulk TCP retransmission, and per-world
  seeded netem churn (the tier-0 pins).
* One compiled graph: ensemble.run_until serves every world of a
  stacked batch from a single jit cache entry.
* HLO identity for solo runs: using the ensemble machinery leaves the
  solo engine's lowering byte-identical -- worlds that never stack pay
  zero compiled ops for the subsystem's existence.
* RNG hygiene: world 0 of a replicate() is bitwise the solo build with
  the same seed (world_key identity at 0); worlds k>0 build from
  independent PURPOSE_WORLD-folded keys, reproducible solo by passing
  the folded key as the builder seed.
* Loud refusals: stack() names the first mismatched block/static and
  points at --bucket; checkpoint.load refuses MISMATCHED world counts
  by name (stacked checkpoints otherwise round-trip, and world=K
  slices one member solo, bitwise); shadow1-tpu diff refuses ensemble
  digest records and points at tools/parse.py ensemble.
* Ensemble resilience (docs/robustness.md "Ensemble resilience"):
  stacked anchors resume bitwise per world; a deterministic failure
  confined to world k quarantines exactly that world (frozen at
  FROZEN_NOW across chunk boundaries) while survivors finish bitwise;
  crash.json carries the per-world roster; replay --world K replays
  one member off the stacked anchors.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import checkpoint, ensemble, sim
from shadow1_tpu import diff as diff_mod
from shadow1_tpu.core import engine, rng, simtime
from shadow1_tpu.core.state import world_count

SEC = simtime.SIMTIME_ONE_SECOND


# ---------------------------------------------------------------- helpers

def _mismatched_leaves(solo, world_slice):
    """Names of leaves where a sliced-out world differs from the solo
    run -- empty means bitwise leaf-for-leaf identical."""
    paths = jax.tree_util.tree_flatten_with_path(solo)[0]
    leaves = jax.tree_util.tree_leaves(world_slice)
    assert len(paths) == len(leaves)
    return [jax.tree_util.keystr(p)
            for (p, a), b in zip(paths, leaves)
            if not np.array_equal(np.asarray(a), np.asarray(b))]


def _assert_worlds_equal_solo(worlds, horizon):
    estate, eparams, app = ensemble.stack(worlds)
    out = ensemble.run_until(estate, eparams, app, horizon)
    for k, (s, p, a) in enumerate(worlds):
        solo = engine.run_until(s, p.replace(megakernel=False), a,
                                horizon)
        wk = jax.tree_util.tree_map(lambda x, k=k: x[k], out)
        bad = _mismatched_leaves(solo, wk)
        assert not bad, f"world {k} diverged from solo at {bad[:6]}"


def _phold(seed, rx_batch=1):
    s, p, a = sim.build_phold(num_hosts=32, msgs_per_host=2,
                              stop_time=3 * SEC, pool_capacity=32 * 8,
                              seed=seed, rx_batch=rx_batch)
    return s, p.replace(megakernel=False), a


def _bulk(seed):
    s, p, a = sim.build_bulk(num_hosts=8, bytes_per_client=1 << 16,
                             reliability=0.98, stop_time=5 * SEC,
                             seed=seed, pool_capacity=1 << 10)
    return s, p.replace(megakernel=False), a


def _churn(seed, n_events=128):
    # Chaos timelines draw seed-dependent event counts; the shared
    # n_events bucket (sim.add_churn passthrough) makes them stack.
    s, p, a = _phold(seed)
    s, p = sim.add_churn(s, p, 0.5, mean_down_s=1.0, n_events=n_events)
    return s, p, a


# ------------------------------------------- tier-0 bitwise solo pins

@pytest.mark.tier0
def test_world_bitwise_equals_solo_phold():
    _assert_worlds_equal_solo([_phold(1), _phold(7)], 2 * SEC)


@pytest.mark.tier0
def test_world_bitwise_equals_solo_phold_rx_batch2():
    _assert_worlds_equal_solo(
        [_phold(1, rx_batch=2), _phold(7, rx_batch=2)], 2 * SEC)


@pytest.mark.tier0
def test_world_bitwise_equals_solo_lossy_tcp():
    _assert_worlds_equal_solo([_bulk(3), _bulk(11)], 2 * SEC)


@pytest.mark.tier0
def test_world_bitwise_equals_solo_netem_churn():
    _assert_worlds_equal_solo([_churn(4), _churn(13)], 2 * SEC)


# ------------------------------------------------ graph + HLO identity

def test_one_compiled_graph_serves_every_world():
    worlds = [_phold(s) for s in (1, 7, 9)]
    estate, eparams, app = ensemble.stack(worlds)
    before = ensemble.cache_size()
    out = ensemble.run_until(estate, eparams, app, SEC)
    out = ensemble.run_until(out, eparams, app, 2 * SEC)
    jax.block_until_ready(out)
    assert ensemble.cache_size() - before <= 1


def test_solo_hlo_identical_after_ensemble_use():
    # The engine's solo lowering must not know the ensemble exists:
    # byte-identical HLO before and after stacking + running a batch
    # in the same process (run_until_impl has no world-axis branches).
    s, p, a = _phold(5)
    txt_before = engine.run_until.lower(s, p, a, SEC).as_text()
    _assert_worlds_equal_solo([_phold(5), _phold(6)], SEC)
    txt_after = engine.run_until.lower(s, p, a, SEC).as_text()
    assert txt_before == txt_after


def test_ensemble_chunked_matches_solo_chunked():
    # Chunk boundaries repartition windows, so chunked and un-chunked
    # runs legitimately differ; the contract is grid-for-grid: the
    # ensemble on a chunk grid equals each world run solo on the SAME
    # grid.
    worlds = [_phold(2), _phold(8)]
    estate, eparams, app = ensemble.stack(worlds)
    out = ensemble.run_chunked(estate, eparams, app, 2 * SEC,
                               chunk_ns=SEC)
    for k, (s, p, a) in enumerate(worlds):
        solo = engine.run_chunked(s, p.replace(megakernel=False), a,
                                  2 * SEC, chunk_ns=SEC)
        wk = jax.tree_util.tree_map(lambda x, k=k: x[k], out)
        bad = _mismatched_leaves(solo, wk)
        assert not bad, f"world {k} diverged from solo-chunked: {bad[:6]}"


# ------------------------------------------------------- RNG hygiene

def test_world_key_identity_at_zero():
    key = rng.root_key(5)
    assert np.array_equal(np.asarray(rng.world_key(key, 0)),
                          np.asarray(key))


def test_world_key_folds_are_distinct_and_deterministic():
    key = rng.root_key(5)
    k1, k2 = rng.world_key(key, 1), rng.world_key(key, 2)
    assert not np.array_equal(np.asarray(k1), np.asarray(key))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.array_equal(np.asarray(k1),
                          np.asarray(rng.world_key(key, 1)))


def test_replicate_world_is_solo_build_with_folded_seed():
    kw = dict(num_hosts=16, msgs_per_host=2, stop_time=SEC,
              pool_capacity=16 * 8)
    worlds = ensemble.replicate(sim.build_phold, 2, seed=5, **kw)
    # World 0: bitwise the plain seed-5 build (identity fold).
    s0, p0, _ = sim.build_phold(seed=5, **kw)
    assert not _mismatched_leaves((s0, p0), (worlds[0][0], worlds[0][1]))
    # World 1: bitwise the solo build seeded with the folded key -- the
    # recipe for reproducing any ensemble member as a solo run.
    k1 = rng.world_key(rng.root_key(5), 1)
    s1, p1, _ = sim.build_phold(seed=k1, **kw)
    assert not _mismatched_leaves((s1, p1), (worlds[1][0], worlds[1][1]))


# ------------------------------------------------------ loud refusals

def test_stack_refuses_shape_mismatch_naming_world_and_bucket():
    a = sim.build_phold(num_hosts=16, stop_time=SEC,
                        pool_capacity=16 * 8)
    b = sim.build_phold(num_hosts=32, stop_time=SEC,
                        pool_capacity=32 * 8)
    with pytest.raises(ensemble.EnsembleMismatch) as ei:
        ensemble.stack([a, b])
    msg = str(ei.value)
    assert "world 1" in msg
    assert "--bucket" in msg


def test_stack_refuses_app_mismatch():
    with pytest.raises(ensemble.EnsembleMismatch):
        ensemble.stack([_phold(1), _bulk(1)])


def test_world_count_probe():
    s, p, a = _phold(1)
    assert world_count(s) is None
    estate, _, _ = ensemble.stack([_phold(1), _phold(2), _phold(3)])
    assert world_count(estate) == 3


def test_checkpoint_stacked_round_trip(tmp_path):
    # Checkpoint v2: stacked states save with per-world manifest
    # coordinates and load back bitwise into an equal-count template.
    estate, eparams, app = ensemble.stack([_phold(1), _phold(2)])
    estate = ensemble.run_until(estate, eparams, app, SEC)
    path = str(tmp_path / "w.npz")
    checkpoint.save(path, estate, eparams)
    man = checkpoint.read_manifest(path)
    assert man["n_worlds"] == 2
    assert len(man["windows"]) == 2 and len(man["t_ns_worlds"]) == 2
    assert man["frozen"] == []
    tes, tep, _ = ensemble.stack([_phold(1), _phold(2)])
    ls, lp = checkpoint.load(path, tes, tep)
    assert not _mismatched_leaves((estate, eparams), (ls, lp))


def test_checkpoint_load_world_slice_bitwise(tmp_path):
    # load(world=K) slices member K solo, bitwise ensemble.world's view
    # (the anchor `replay --world K` restores).  The members ask for
    # the fused path, which stack() must force off.
    estate, eparams, app = ensemble.stack(
        [(s, p.replace(megakernel=True), a)
         for s, p, a in (_phold(1), _phold(2))])
    estate = ensemble.run_until(estate, eparams, app, SEC)
    path = str(tmp_path / "w.npz")
    checkpoint.save(path, estate, eparams)
    s, p, _ = _phold(2)
    ws, wp = checkpoint.load(path, s, p, world=1)
    ref_s, ref_p = ensemble.world(estate, eparams, 1)
    assert not _mismatched_leaves((ref_s, ref_p), (ws, wp))
    assert bool(wp.megakernel) is False  # stack() forced it off


def test_checkpoint_load_refuses_world_mismatch(tmp_path):
    # Mismatched world counts are refused by NAME, both directions.
    estate, eparams, _ = ensemble.stack([_phold(1), _phold(2)])
    path = str(tmp_path / "w.npz")
    checkpoint.save(path, estate, eparams)
    s, p, _ = _phold(1)
    with pytest.raises(ValueError, match="--worlds 2"):
        checkpoint.load(path, s, p)          # solo template
    t3 = ensemble.stack([_phold(1), _phold(2), _phold(3)])
    with pytest.raises(ValueError, match="--worlds 2"):
        checkpoint.load(path, t3[0], t3[1])  # 3-world template
    solo = str(tmp_path / "solo.npz")
    checkpoint.save(solo, s, p)
    with pytest.raises(ValueError, match="solo"):
        checkpoint.load(solo, s, p, world=0)  # world slice of a solo


def test_checkpoint_load_refuses_ensemble_stamp(tmp_path):
    s, p, _ = _phold(1)
    path = str(tmp_path / "w.npz")
    checkpoint.save(path, s, p, manifest={"n_worlds": 2, "world": 1})
    with pytest.raises(ValueError, match="--worlds 2"):
        checkpoint.load(path, s, p)


def test_shard_worlds_requires_divisibility():
    from shadow1_tpu import parallel
    estate, eparams, _ = ensemble.stack(
        [_phold(1), _phold(2), _phold(3)])
    mesh = parallel.make_mesh(jax.devices()[:2])
    with pytest.raises(ValueError, match="divide"):
        ensemble.shard_worlds(estate, eparams, mesh)


# ------------------------------------------------- run_ensemble + CLI

def test_run_ensemble_artifacts_and_diff_refusal(tmp_path):
    data = str(tmp_path / "run")
    worlds = [_phold(1), _phold(7)]
    estate, eparams, app, summaries = sim.run_ensemble(
        worlds, until=SEC, data_dir=data, digest=2, heartbeat_s=1)
    assert [s["world"] for s in summaries] == [0, 1]
    assert all(s["events"] > 0 for s in summaries)

    info = json.load(open(os.path.join(data, "ckpt", "run.json")))
    assert info["n_worlds"] == 2

    with open(os.path.join(data, "heartbeat.csv")) as f:
        header = f.readline()
        assert header.startswith("world,")
        seen = {line.split(",", 1)[0] for line in f if line.strip()}
    assert seen == {"0", "1"}

    with open(os.path.join(data, "digests.jsonl")) as f:
        dworlds = {json.loads(line)["world"] for line in f
                   if line.strip()}
    assert dworlds == {0, 1}

    summary = json.load(open(os.path.join(data, "summary.json")))
    assert summary["n_worlds"] == 2
    assert len(summary["worlds"]) == 2

    # Statescope diff refuses ensemble records by name and points at
    # the ensemble-aware reader instead of mis-joining world streams.
    with pytest.raises(ValueError, match="parse.py ensemble"):
        diff_mod.diff_runs(data, data)


def test_cli_sweep_overrides():
    import argparse

    from shadow1_tpu import cli

    ns = argparse.Namespace(sweep=None, worlds=3, seed=5)
    overrides, spec = cli._sweep_overrides(ns)
    assert overrides == [{"seed": 5}, {"seed": 6}, {"seed": 7}]
    assert spec is None


def test_cli_sweep_spec_refusals(tmp_path):
    import argparse

    from shadow1_tpu import cli

    def run(spec_obj, worlds=1):
        path = tmp_path / "sweep.json"
        path.write_text(json.dumps(spec_obj))
        ns = argparse.Namespace(sweep=str(path), worlds=worlds, seed=1)
        return cli._sweep_overrides(ns)

    overrides, spec = run({"seeds": [4, 9]})
    assert overrides == [{"seed": 4}, {"seed": 9}]
    assert spec == {"seeds": [4, 9]}

    overrides, _ = run({"worlds": [{"seed": 2, "churn": 0.5}, {}]})
    assert overrides[0] == {"seed": 2, "churn": 0.5}
    assert overrides[1] == {"seed": 2}  # base seed 1 + world index 1

    with pytest.raises(cli.CliError, match="non-empty list of integers"):
        run({"seeds": [1, "x"]})
    with pytest.raises(cli.CliError, match="only"):
        run({"worlds": [{"seed": 1, "pool_slab": 9}]})
    with pytest.raises(cli.CliError, match="--worlds 3"):
        run({"seeds": [1, 2]}, worlds=3)


# ------------------------------------------- ensemble resilience
#
# docs/robustness.md "Ensemble resilience": stacked checkpoints,
# per-world sentinel verdicts, Supervisor world quarantine, and
# --auto-resume for ensembles.  tools/faultdrill.py's `ensemble`
# drill covers the real-SIGKILL subprocess version; these tests pin
# the same contracts in-process.

# Bit pattern of a float64 NaN, written into the INTEGER srtt leaf --
# the sentinel's nonfinite probe trips on it (the timer-plausibility
# ceiling is far below; same mechanism as faultdrill's nan drills).
NAN_BITS = 9221120237041090560

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _installed_phold(seed):
    """A _phold world carrying the blocks run_ensemble installs for a
    checkpointed + supervised run -- the template a stacked anchor of
    such a run loads back into."""
    from shadow1_tpu import trace
    s, p, a = _phold(seed)
    s = trace.ensure_flight_recorder(s, shards=1)
    s = trace.ensure_sentinel(s)
    return s, p, a


def _newest_anchor(data_dir):
    import glob
    paths = glob.glob(os.path.join(data_dir, "ckpt", "win_*.npz"))
    assert paths
    return max(paths,
               key=lambda p: int(os.path.basename(p)[4:-4]))


def _world_rows(path):
    """windows.jsonl rows keyed by world column -- per-world byte
    comparison (cross-world interleave is drain-order, not part of
    the bitwise contract once a quarantine flush perturbs it)."""
    rows = {}
    with open(path, "rb") as f:
        for line in f:
            if line.strip():
                rows.setdefault(json.loads(line)["world"],
                                []).append(line)
    return rows


def test_run_chunked_keeps_frozen_lanes_parked():
    # A quarantined lane is parked at FROZEN_NOW; the engine tail
    # rewrites `now` after every inner chunk, so run_chunked must
    # re-freeze at each boundary or the lane thaws mid-attempt.
    estate, eparams, app = ensemble.stack([_phold(1), _phold(2)])
    half = ensemble.run_until(estate, eparams, app, SEC)
    frozen = ensemble.freeze_worlds(half, [0])
    out = ensemble.run_chunked(frozen, eparams, app, 2 * SEC,
                               chunk_ns=SEC // 4)
    assert ensemble.frozen_worlds(out) == [0]
    # The parked lane carried nothing but its (re-frozen) clock.
    diff = _mismatched_leaves(ensemble.world(frozen, eparams, 0),
                              ensemble.world(out, eparams, 0))
    assert all("now" in d for d in diff), diff
    # The survivor is bitwise the never-frozen chunked run.
    ref = ensemble.run_chunked(half, eparams, app, 2 * SEC,
                               chunk_ns=SEC // 4)
    assert not _mismatched_leaves(ensemble.world(ref, eparams, 1),
                                  ensemble.world(out, eparams, 1))


@pytest.mark.tier0
def test_run_ensemble_auto_resume_bitwise(tmp_path):
    # Tier-0 pin: an interrupted supervised 4-world run resumed from
    # its newest stacked anchor finishes leaf-for-leaf bitwise equal,
    # per world, to the uninterrupted ensemble, and windows.jsonl
    # re-records the same per-world rows.
    seeds = (3, 5, 7, 11)
    kw = dict(checkpoint_every=SEC, supervise=True)
    ref_dir = str(tmp_path / "ref")
    ref = sim.run_ensemble([_phold(s) for s in seeds], until=3 * SEC,
                           data_dir=ref_dir, **kw)
    res_dir = str(tmp_path / "res")
    # "Kill": abandon mid-flight past the 1s anchor -- anchors plus a
    # windows.jsonl trail are all a SIGKILL leaves behind.
    sim.run_ensemble([_phold(s) for s in seeds],
                     until=SEC + SEC // 2, data_dir=res_dir, **kw)
    out = sim.run_ensemble([_phold(s) for s in seeds], until=3 * SEC,
                           data_dir=res_dir, resume=True, **kw)
    for k in range(len(seeds)):
        assert not _mismatched_leaves(
            ensemble.world(ref[0], ref[1], k),
            ensemble.world(out[0], out[1], k)), f"world {k}"
    assert _world_rows(os.path.join(ref_dir, "windows.jsonl")) == \
        _world_rows(os.path.join(res_dir, "windows.jsonl"))
    info = json.load(open(os.path.join(res_dir, "ckpt", "run.json")))
    assert info["n_worlds"] == len(seeds)


def test_run_ensemble_quarantines_poisoned_world(tmp_path):
    # A deterministic failure confined to world 2 (NaN bits planted
    # in its srtt lane in the newest stacked anchor) quarantines that
    # world -- frozen at FROZEN_NOW -- while the survivors finish;
    # crash.json doubles as the per-world evidence roster.
    seeds = (3, 5, 7, 11)
    data = str(tmp_path / "run")
    kw = dict(checkpoint_every=SEC, supervise=True)
    sim.run_ensemble([_phold(s) for s in seeds], until=SEC,
                     data_dir=data, **kw)
    path = _newest_anchor(data)
    tes, tep, _ = ensemble.stack([_installed_phold(s) for s in seeds])
    man = checkpoint.read_manifest(path)
    ls, lp = checkpoint.load(path, tes, tep)
    srtt = np.asarray(ls.socks.srtt).copy()
    srtt[2, 0, 1] = np.int64(NAN_BITS)
    ls = ls.replace(socks=ls.socks.replace(srtt=srtt))
    checkpoint.save(path, ls, lp, manifest=man)

    estate, eparams, app, summaries = sim.run_ensemble(
        [_phold(s) for s in seeds], until=2 * SEC, data_dir=data,
        resume=True, **kw)
    assert ensemble.frozen_worlds(estate) == [2]
    assert [s["quarantined"] for s in summaries] == \
        [False, False, True, False]
    assert all(s["events"] > 0 for k, s in enumerate(summaries)
               if k != 2)

    summary = json.load(open(os.path.join(data, "summary.json")))
    assert summary["supervise"]["quarantined"] == [2]
    crash = json.load(open(os.path.join(data, "crash.json")))
    roster = crash["worlds"]
    assert roster["n_worlds"] == len(seeds)
    assert roster["quarantined"] == [2]
    (member,) = roster["members"]
    assert member["world"] == 2
    assert "--world 2" in member["replay"]


class TestCliEnsembleResilience:
    CONFIG = os.path.join(REPO, "examples", "tgen-2host",
                          "shadow.config.xml")

    def test_flag_validation_names_the_knob(self, capsys, tmp_path):
        from shadow1_tpu import cli
        from shadow1_tpu.supervise import RC_USAGE
        rc = cli.main(["run", self.CONFIG, "--worlds", "2",
                       "--auto-resume"])
        assert rc == RC_USAGE
        assert "--checkpoint-every" in capsys.readouterr().err
        rc = cli.main(["run", self.CONFIG, "--worlds", "2",
                       "--checkpoint-every", "2"])
        assert rc == RC_USAGE
        assert "--data-directory" in capsys.readouterr().err
        rc = cli.main(["run", self.CONFIG, "--worlds", "2",
                       "--checkpoint-every", "2", "--data-directory",
                       str(tmp_path), "--watchdog", "60"])
        assert rc == RC_USAGE
        assert "--auto-resume" in capsys.readouterr().err

    def test_replay_world_member_and_refusals(self, tmp_path, capsys):
        from shadow1_tpu import cli
        from shadow1_tpu.supervise import RC_OK, RC_USAGE
        d = str(tmp_path / "ens")
        assert cli.main(["run", self.CONFIG, "--worlds", "2",
                         "--checkpoint-every", "2", "--stop-time", "4",
                         "--data-directory", d, "--auto-resume",
                         "--quiet"]) == RC_OK
        capsys.readouterr()
        # One member replays solo off the stacked anchors, verified
        # bitwise against its own windows.jsonl rows.
        assert cli.main(["replay", "--data-directory", d,
                         "--world", "1", "--quiet"]) == RC_OK
        capsys.readouterr()
        # Ensemble run without --world: refused by name.
        rc = cli.main(["replay", "--data-directory", d, "--quiet"])
        assert rc == RC_USAGE
        assert "--world" in capsys.readouterr().err
        # Solo run with --world: refused by name.
        solo = str(tmp_path / "solo")
        assert cli.main(["run", self.CONFIG, "--checkpoint-every", "2",
                         "--stop-time", "4", "--data-directory", solo,
                         "--auto-resume", "--quiet"]) == RC_OK
        capsys.readouterr()
        rc = cli.main(["replay", "--data-directory", solo,
                       "--world", "0", "--quiet"])
        assert rc == RC_USAGE
        assert "solo" in capsys.readouterr().err
