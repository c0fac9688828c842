"""Self-healing runs: the supervisor's failure-handling contract.

docs/robustness.md promises:

* A unified exit-code table (0 ok / 1 simulation-wrong / 2 usage /
  3 unrecovered-infrastructure) that classify() and
  UnrecoveredFailure.rc map failures onto.
* A degradation ladder (retry -> megakernel off -> halve chunk ->
  gather single) where every rung re-executes from the newest readable
  checkpoint, every rung is bitwise-neutral, deterministic failure
  classes skip plain retry, and exhaustion surrenders with a
  structured crash.json.
* Supervised runs are bitwise identical to unsupervised ones on the
  same launch grid, and a run that RECOVERS produces the same final
  state it would have produced without the failure.
* Auto-resume plumbing: trim_windows keeps windows.jsonl contiguous,
  FlightDrain(mode="a") appends across process lifetimes, and the CLI
  refuses --auto-resume/--watchdog misuse with rc 2.

tools/faultdrill.py drills the same machinery end to end through real
subprocesses (SIGKILL, torn checkpoint files, poisoned saves).
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from shadow1_tpu import checkpoint, cli, replay, sim, supervise, trace
from shadow1_tpu.core import engine, simtime
from shadow1_tpu.core.state import (SENTINEL_BOUNDS, SENTINEL_NONFINITE,
                                    SENTINEL_TIME)

SEC = simtime.SIMTIME_ONE_SECOND

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAN_BITS = 9221120237041090560

BULK_KW = dict(num_hosts=6, bytes_per_client=1 << 14, reliability=0.9,
               stop_time=8 * SEC)


def _bulk():
    return sim.build_bulk(**BULK_KW)


def _ckrun(ckdir, supervise_opt=None, stop=2 * SEC, megakernel=False):
    # The bulk world is all done by ~1.5s, so a 0.5s cadence leaves
    # several MID-ACTIVITY checkpoints -- poison anchored there is
    # guaranteed to be followed by executed (= sentinel-checked)
    # windows, which a cadence past the activity tail would not.
    state, params, app = _bulk()
    params = params.replace(megakernel=megakernel)
    out = sim.run(state, params, app, until=stop,
                  checkpoint_every=SEC // 2, checkpoint_dir=str(ckdir),
                  checkpoint_world=("bulk", BULK_KW),
                  supervise=supervise_opt)
    return out, params, app


def _poison_mid(d, megakernel=False):
    """NaN-poison the srtt leaf of the run's second checkpoint, drop
    every later one, and return (path, manifest, built-world).  The
    rebuilt params carry the run's `megakernel` static (a ShapeKey
    field the checkpoint is stamped with)."""
    idx_path = os.path.join(d, "ckpt", "index.json")
    with open(idx_path) as f:
        idx = json.load(f)
    entries = sorted(idx["checkpoints"], key=lambda e: e["window"])
    assert len(entries) >= 3, entries
    for e in entries[2:]:
        os.remove(os.path.join(d, "ckpt", e["file"]))
    idx["checkpoints"] = entries[:2]
    with open(idx_path, "w") as f:
        json.dump(idx, f)

    info = replay.load_run(d)
    built = replay.rebuild_world(info, d, want_mesh=False)
    built["params"] = built["params"].replace(megakernel=megakernel)
    path = os.path.join(d, "ckpt", entries[1]["file"])
    man = checkpoint.read_manifest(path)
    state, params = checkpoint.load(path, built["state"],
                                    built["params"])
    srtt = np.asarray(state.socks.srtt).copy()
    srtt[0, 1] = np.int64(NAN_BITS)
    state = state.replace(socks=state.socks.replace(srtt=srtt))
    checkpoint.save(path, state, params, manifest=man)
    return path, man, built


def _violation(bits):
    return trace.SentinelViolation(
        {"violations": bits, "first_bad_window": 3,
         "first_bad_t": 123, "classes": trace.sentinel_classes(bits)})


class TestRcTable:
    def test_values(self):
        assert supervise.RC_OK == 0
        assert supervise.RC_INVARIANT == 1
        assert supervise.RC_USAGE == 2
        assert supervise.RC_FAILED == 3

    def test_unrecovered_rc_splits_on_determinism(self):
        # A deterministic failure means the SIMULATION is wrong (rc 1,
        # replayable); infrastructure failures are rc 3.
        for cls, rc in (("nan", 1), ("sentinel", 1), ("oom", 3),
                        ("hung", 3), ("interrupted", 3), ("error", 3)):
            e = supervise.UnrecoveredFailure(
                {"failure": {"class": cls, "message": "x"}}, "/nowhere")
            assert e.rc == rc, cls


class TestClassify:
    def test_sentinel_violations(self):
        # Pure non-finiteness is the NaN class; any logic-invariant bit
        # (alone or mixed in) is the sentinel class.
        assert supervise.classify(
            _violation(SENTINEL_NONFINITE)) == supervise.F_NAN
        assert supervise.classify(
            _violation(SENTINEL_BOUNDS)) == supervise.F_SENTINEL
        assert supervise.classify(
            _violation(SENTINEL_NONFINITE
                       | SENTINEL_TIME)) == supervise.F_SENTINEL

    def test_host_exceptions(self):
        assert supervise.classify(
            KeyboardInterrupt()) == supervise.F_INTERRUPTED
        assert supervise.classify(
            supervise.HungLaunch("x")) == supervise.F_HUNG
        assert supervise.classify(
            FloatingPointError("nan in op")) == supervise.F_NAN
        assert supervise.classify(RuntimeError(
            "RESOURCE_EXHAUSTED: allocating 2G")) == supervise.F_OOM
        assert supervise.classify(
            RuntimeError("device Out Of Memory")) == supervise.F_OOM
        assert supervise.classify(RuntimeError("boom")) == \
            supervise.F_ERROR

    def test_deterministic_set(self):
        assert supervise.DETERMINISTIC == {supervise.F_SENTINEL,
                                           supervise.F_NAN}


class TestTrimWindows:
    def test_trims_at_or_after_and_torn_lines(self, tmp_path):
        p = tmp_path / "windows.jsonl"
        lines = [json.dumps({"window": w, "x": w * 10}) for w in range(5)]
        p.write_text("\n".join(lines) + "\n" + '{"window": 5, "tor')
        dropped = supervise.trim_windows(str(p), 2)
        assert dropped == 4  # windows 2,3,4 + the torn tail line
        kept = [json.loads(s) for s in p.read_text().splitlines()]
        assert [r["window"] for r in kept] == [0, 1]

    def test_missing_file_is_zero(self, tmp_path):
        assert supervise.trim_windows(str(tmp_path / "nope.jsonl"),
                                      0) == 0


class TestFlightDrainAppend:
    def test_append_mode_preserves_existing_rows(self, tmp_path):
        p = tmp_path / "windows.jsonl"
        p.write_text('{"window": 0}\n')
        fd = trace.FlightDrain(str(p), mode="a")
        fd.close()
        assert p.read_text() == '{"window": 0}\n'
        fd = trace.FlightDrain(str(p))  # default truncates
        fd.close()
        assert p.read_text() == ""


class TestSupervisedRun:
    def test_requires_checkpointing(self):
        state, params, app = _bulk()
        with pytest.raises(ValueError, match="checkpoint"):
            sim.run(state, params, app, supervise=True)

    def test_clean_run_bitwise_neutral_and_stamped(self, tmp_path):
        sup_out, params, app = _ckrun(tmp_path / "sup",
                                      supervise_opt=True)
        bare_out, _, _ = _ckrun(tmp_path / "bare")
        assert sup_out.sentinel is not None and bare_out.sentinel is None
        la, ta = jax.tree_util.tree_flatten(bare_out)
        lb, tb = jax.tree_util.tree_flatten(
            sup_out.replace(sentinel=None))
        assert ta == tb
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        info = replay.load_run(str(tmp_path / "sup"))
        assert info["sentinel"] is True and info["supervise"] is True
        assert not os.path.exists(tmp_path / "sup" / "crash.json")

    def test_transient_failure_recovers_bitwise(self, tmp_path,
                                                monkeypatch):
        # A one-shot nondeterministic launch failure: the retry rung
        # reloads the newest checkpoint and the run completes with the
        # SAME final state as a clean run -- recovery never forks.
        clean, params, app = _ckrun(tmp_path / "clean",
                                    supervise_opt=True)
        real = engine.run_chunked
        boom = {"left": 1}

        def flaky(*a, **kw):
            if boom["left"]:
                boom["left"] -= 1
                raise RuntimeError("transient backend hiccup")
            return real(*a, **kw)

        monkeypatch.setattr(engine, "run_chunked", flaky)
        out, _, _ = _ckrun(tmp_path / "flaky", supervise_opt=True)
        la, ta = jax.tree_util.tree_flatten(clean)
        lb, tb = jax.tree_util.tree_flatten(out)
        assert ta == tb
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not os.path.exists(tmp_path / "flaky" / "crash.json")

    def test_poisoned_resume_walks_ladder_to_crash_json(self, tmp_path):
        # The acceptance scenario in miniature: a NaN bit pattern lands
        # in a checkpointed srtt lane; resuming from it must trip the
        # sentinel in the first window, skip plain retry (deterministic
        # class), exhaust the bitwise-neutral rungs, and surrender rc 1
        # with a complete crash report.
        # The run asks for the fused path, so the megakernel_off rung
        # has something to turn off.
        d = str(tmp_path)
        _ckrun(d, supervise_opt=True, megakernel=True)
        path, man, built = _poison_mid(d, megakernel=True)
        state, params = checkpoint.load(path, built["state"],
                                        built["params"])
        sup = supervise.Supervisor(d, built["app"], quiet=True,
                                   resume_cmd="resume-me")
        with pytest.raises(supervise.UnrecoveredFailure) as ei:
            sup.launch(state, params, int(man["t_ns"]) + 2 * SEC)
        e = ei.value
        assert e.rc == supervise.RC_INVARIANT
        crash = json.loads((tmp_path / "crash.json").read_text())
        assert crash == e.crash
        assert crash["failure"]["class"] == "nan"
        assert crash["window"] == int(man["window"])
        assert crash["sentinel"]["classes"] == ["nonfinite"]
        assert crash["checkpoint"]["file"] == os.path.basename(path)
        assert crash["resume"] == "resume-me"
        assert f"--window {crash['window']}" in crash["replay"]
        # The full ladder: retry skipped (deterministic), megakernel
        # and chunk rungs taken, gather skipped (already single-device).
        trail = {r["rung"]: r["action"] for r in crash["ladder"]}
        assert trail == {"retry": "skipped", "megakernel_off": "taken",
                         "halve_chunk": "taken",
                         "gather_single": "skipped"}
        assert sup.recoveries == 2

    def test_megakernel_off_is_per_launch_not_params(self, tmp_path):
        # The rung overrides a COPY per launch; the caller's params (and
        # therefore every checkpoint's static stamp) keep the canonical
        # megakernel flag, so replay templates stay valid.
        state, params, app = _bulk()
        params = params.replace(megakernel=True)
        seen = []

        sup = supervise.Supervisor(str(tmp_path), app, quiet=True)
        sup.megakernel_off = True
        real = engine.run_chunked
        try:
            engine.run_chunked = lambda st, pr, ap, t, **kw: (
                seen.append(pr), st)[1]
            out = sup.launch(state, params, SEC)
        finally:
            engine.run_chunked = real
        assert out is state
        assert seen[0].megakernel is False
        assert params.megakernel is True

    def test_watchdog_surrenders_hung_rc3(self, tmp_path):
        state, params, app = _bulk()
        sup = supervise.Supervisor(str(tmp_path), app, quiet=True,
                                   watchdog_s=0.2)
        sup._warm = True  # past the compile grace: deadline is armed
        real = engine.run_chunked
        try:
            engine.run_chunked = \
                lambda *a, **kw: time.sleep(30)
            with pytest.raises(supervise.UnrecoveredFailure) as ei:
                sup.launch(state, params, SEC)
        finally:
            engine.run_chunked = real
        assert ei.value.rc == supervise.RC_FAILED
        crash = json.loads((tmp_path / "crash.json").read_text())
        assert crash["failure"]["class"] == "hung"
        assert crash["ladder"] == []  # no in-process recovery attempted

    def test_watchdog_compile_grace(self, tmp_path):
        # Regression: the watchdog must be armed only after the first
        # launch of the current graph completes.  A cold launch pays
        # XLA compilation, which can dwarf any sane deadline -- before
        # the fix a tight --watchdog rc-3-surrendered every cold run.
        state, params, app = _bulk()
        sup = supervise.Supervisor(str(tmp_path), app, quiet=True,
                                   watchdog_s=0.2)
        assert sup._warm is False
        real = engine.run_chunked
        try:
            # "Compile" for 0.6s, far past the 0.2s deadline: the cold
            # launch must complete anyway.
            engine.run_chunked = lambda st, *a, **kw: (time.sleep(0.6),
                                                       st)[1]
            out = sup.launch(state, params, SEC)
            assert out is state and sup._warm is True
            # The SAME slow launch warm is a genuine hang: rc 3.
            with pytest.raises(supervise.UnrecoveredFailure) as ei:
                sup.launch(state, params, 2 * SEC)
        finally:
            engine.run_chunked = real
        assert ei.value.rc == supervise.RC_FAILED
        assert json.loads((tmp_path / "crash.json").read_text())[
            "failure"]["class"] == "hung"

    def test_watchdog_world_count_grace(self, tmp_path):
        # A launch whose n_worlds differs from the previous graph's
        # re-opens the compile grace: a vmapped ensemble graph compiles
        # slower than the solo one it follows, and that cold compile
        # must not classify as hung (mirrors the megakernel_off /
        # gather_single grace).
        from shadow1_tpu import ensemble
        state, params, app = _bulk()
        sup = supervise.Supervisor(str(tmp_path), app, quiet=True,
                                   watchdog_s=0.2)
        real, ereal = engine.run_chunked, ensemble.run_chunked
        try:
            slow = lambda st, *a, **kw: (time.sleep(0.6), st)[1]
            engine.run_chunked = slow
            ensemble.run_chunked = slow
            sup.launch(state, params, SEC)
            assert sup._warm is True and sup._graph_worlds is None
            # Stack 2 worlds: a NEW graph, so the slow cold launch
            # must complete despite the armed 0.2s deadline.
            estate, eparams, _ = ensemble.stack([_bulk(), _bulk()])
            out = sup.launch(estate, eparams, SEC)
            assert out is estate
            assert sup._warm is True and sup._graph_worlds == 2
            # The SAME slow ensemble launch warm is a genuine hang.
            with pytest.raises(supervise.UnrecoveredFailure) as ei:
                sup.launch(estate, eparams, 2 * SEC)
        finally:
            engine.run_chunked = real
            ensemble.run_chunked = ereal
        assert ei.value.rc == supervise.RC_FAILED
        assert json.loads((tmp_path / "crash.json").read_text())[
            "failure"]["class"] == "hung"

    def test_watchdog_overlap_grace(self, tmp_path):
        # Regression for the async window pipeline: launch() runs the
        # overlap hook -- the pipeline's drain point for the PREVIOUS
        # window -- on the calling thread while the device executes,
        # and the watchdog deadline is measured from AFTER the hook
        # returns.  A host-side drain longer than --watchdog says
        # nothing about a wedged device and must not rc-3.
        state, params, app = _bulk()
        sup = supervise.Supervisor(str(tmp_path), app, quiet=True,
                                   watchdog_s=0.2)
        sup._warm = True  # armed: no compile grace in play
        drained = []
        real = engine.run_chunked
        try:
            engine.run_chunked = lambda st, *a, **kw: st
            out = sup.launch(state, params, SEC,
                             overlap=lambda: (drained.append(1),
                                              time.sleep(0.6)))
        finally:
            engine.run_chunked = real
        assert out is state and drained == [1]
        assert not (tmp_path / "crash.json").exists()
        # A genuinely wedged device is still caught with a hook
        # present: the hook only moves the measurement point.
        try:
            engine.run_chunked = lambda *a, **kw: time.sleep(30)
            with pytest.raises(supervise.UnrecoveredFailure) as ei:
                sup.launch(state, params, 2 * SEC,
                           overlap=lambda: time.sleep(0.3))
        finally:
            engine.run_chunked = real
        assert ei.value.rc == supervise.RC_FAILED
        assert json.loads((tmp_path / "crash.json").read_text())[
            "failure"]["class"] == "hung"


class TestReplayReproduces:
    def test_replay_reports_sentinel_violation(self, tmp_path):
        # replay of a sentinel-carrying run re-checks the block; a
        # poisoned anchor reproduces the violation deterministically.
        d = str(tmp_path)
        _ckrun(d, supervise_opt=True)
        path, man, built = _poison_mid(d)

        res = replay.replay(d, window=int(man["window"]), verify=False)
        sn = res["sentinel"]
        assert "nonfinite" in sn["classes"]
        assert sn["first_bad_window"] == int(man["window"])


class TestTornStateFiles:
    """A crash can tear any host-side state file; none of them may
    abort a resume.  Checkpoints themselves are atomic, so index.json
    and run.json are rebuildable caches -- and are rebuilt."""

    def test_torn_index_rebuilt_from_manifests(self, tmp_path):
        d = str(tmp_path)
        _ckrun(d, supervise_opt=True)
        idx = tmp_path / "ckpt" / "index.json"
        orig = json.loads(idx.read_text())["checkpoints"]
        raw = idx.read_bytes()
        idx.write_bytes(raw[:len(raw) // 2])  # torn mid-byte
        with pytest.warns(RuntimeWarning, match="rebuilding"):
            ck = replay.Checkpointer(d, SEC // 2)
        assert ck.saved == sorted(orig, key=lambda e: e["window"])
        # The rebuild also rewrote the file, atomically.
        assert json.loads(idx.read_text())["checkpoints"] == ck.saved

    def test_rebuild_index_skips_torn_npz(self, tmp_path):
        d = str(tmp_path)
        _ckrun(d, supervise_opt=True)
        entries = replay.rebuild_index(d)
        victim = os.path.join(d, "ckpt", entries[-1]["file"])
        size = os.path.getsize(victim)
        with open(victim, "r+b") as f:
            f.truncate(size // 2)
        rebuilt = replay.rebuild_index(d)
        assert [e["file"] for e in rebuilt] == \
            [e["file"] for e in entries[:-1]]

    def test_torn_run_json_does_not_abort_cli_resume(self, tmp_path,
                                                     capsys):
        config = os.path.join(REPO, "examples", "tgen-2host",
                              "shadow.config.xml")
        d = str(tmp_path / "run")
        argv = ["run", config, "--checkpoint-every", "2",
                "--stop-time", "4", "--data-directory", d,
                "--auto-resume", "--quiet"]
        assert cli.main(argv) == 0
        capsys.readouterr()
        rj = tmp_path / "run" / "ckpt" / "run.json"
        raw = rj.read_bytes()
        rj.write_bytes(raw[:len(raw) // 2])  # torn mid-byte
        assert cli.main(argv) == 0
        capsys.readouterr()
        # The resume rewrote the recipe from its own flags.
        info = json.loads(rj.read_text())
        assert info["version"] == replay.RUN_JSON_VERSION
        assert info["world"]["kind"] == "config"


class TestCliUsage:
    CONFIG = os.path.join(REPO, "examples", "tgen-2host",
                          "shadow.config.xml")

    def test_auto_resume_requires_checkpointing(self, capsys):
        rc = cli.main(["run", self.CONFIG, "--auto-resume"])
        assert rc == supervise.RC_USAGE
        assert "--checkpoint-every" in capsys.readouterr().err

    def test_watchdog_requires_auto_resume(self, capsys, tmp_path):
        rc = cli.main(["run", self.CONFIG, "--checkpoint-every", "2",
                       "--data-directory", str(tmp_path),
                       "--watchdog", "60"])
        assert rc == supervise.RC_USAGE
        assert "--auto-resume" in capsys.readouterr().err
