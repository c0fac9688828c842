"""Packet lineage: the sampled per-packet span-tracing contract.

docs/observability.md ("Packet lineage") promises five properties for
the `--trace-packets` block:

* Structural zero cost when absent: a world that never had a tracer
  and one that had it attached then detached lower to byte-identical
  HLO (lineage=None is a trace-time static), so untraced runs pay zero
  compiled ops and a zero kernelcount delta.
* Bitwise trajectory neutrality when present: sampling keys off state
  the sim already carries (src host, emission counter) and writes only
  into its own side arrays and span ring; every non-lineage leaf of
  the final state is bitwise identical, on phold (both rx_batch modes)
  and on the lossy bulk-TCP world with real retransmissions.
* Seeded determinism: the sampled packet set is a pure function of
  (src, emission counter), so one device and a 4-shard mesh trace the
  SAME packets and drain the SAME span multisets, and a replay can
  install the tracer after the fact and reproduce the original sample.
* Wrap-proof lifetime totals: the ring loses span ROWS when it wraps,
  never counts -- n_assigned and the append total stay exact, so
  spans + spans_lost always equals the unwrapped run's span count.
* Failure attribution: a packet killed by a netem event carries the
  kill reason (host_down/link_down/...) on its fatal hop.

Plus the protocol checks: the rate-spec parser, idempotent install and
shard validation, megakernel fallback, the off-mesh sharded refusal,
the ShapeKey discriminant, tools/parse.py + tools/plot.py rendering,
the benchdiff config gate, and the two replay satellites (--flight-rows
wrap-proof verify, --window out-of-range message).
"""

import importlib.util
import json
import os
import warnings

import jax
import numpy as np
import pytest

from shadow1_tpu import netem, replay, shapes, sim, trace
from shadow1_tpu.core import engine, megakernel, simtime
from shadow1_tpu.parallel import make_mesh, mesh_run_chunked

MS = simtime.SIMTIME_ONE_MILLISECOND
SEC = simtime.SIMTIME_ONE_SECOND

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phold(**over):
    kw = dict(num_hosts=16, msgs_per_host=2, mean_delay_ns=10 * MS,
              stop_time=2 * SEC, pool_capacity=16 * 8, seed=7)
    kw.update(over)
    return sim.build_phold(**kw)


def _lossy_bulk(**over):
    """The acceptance world: bulk TCP with injected loss, so traced
    packets include retransmitted segments and qdisc drops."""
    kw = dict(num_hosts=6, bytes_per_client=1 << 14, reliability=0.9,
              stop_time=8 * SEC)
    kw.update(over)
    return sim.build_bulk(**kw)


def _drain_chunked(state, params, app, stop_ns, step_ns, runner,
                   spans_path=None):
    """The CLI's lineage loop in miniature: chunked launches with a
    LineageDrain at every boundary."""
    ld = trace.LineageDrain(spans_path=spans_path)
    t = 0
    while t < stop_ns:
        t = min(t + step_ns, stop_ns)
        state = runner(state, t)
        ld.drain(state)
    ld.close()
    return state, ld


# Checkpointed phold run WITHOUT lineage, shared by the replay tests
# (on-demand install, window-range satellite).
KW = dict(num_hosts=8, msgs_per_host=2, stop_time=2 * SEC, seed=3)
EVERY = SEC // 2


@pytest.fixture(scope="module")
def phold_ck(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("lineage_ck"))
    state, params, app = sim.build_phold(**KW)
    sim.run(state, params, app, checkpoint_every=EVERY,
            checkpoint_dir=d, checkpoint_world=("phold", KW))
    return d


class TestRateSpec:
    def test_accepted_forms(self):
        assert trace.parse_lineage_rate(0.25) == 0.25
        assert trace.parse_lineage_rate("0.01") == 0.01
        assert trace.parse_lineage_rate("1%") == 0.01
        assert trace.parse_lineage_rate("all") == 1.0
        assert trace.parse_lineage_rate(1) == 1.0

    def test_bad_specs_raise(self):
        # A fat-fingered `--trace-packets 10` must fail loudly, not
        # silently clamp.
        for bad in ("", "abc", 0, -0.1, 10, "10", "150%"):
            with pytest.raises(ValueError):
                trace.parse_lineage_rate(bad)

    def test_threshold_never_oversamples(self):
        from shadow1_tpu.core.state import lineage_rate_bits
        assert lineage_rate_bits(1.0) == 0xFFFFFFFF
        # Tiny rates must round toward zero samples, never wrap to -1
        # (== sample everything).
        assert lineage_rate_bits(1e-15) == 0
        assert lineage_rate_bits(0.5) <= 0x80000000

    def test_ensure_is_idempotent_and_validates_shards(self):
        state, params, app = _lossy_bulk()
        s1 = trace.ensure_lineage(state)
        assert trace.ensure_lineage(s1) is s1
        with pytest.raises(ValueError, match="pad_world_to_mesh"):
            trace.ensure_lineage(state, shards=4)  # 6 % 4 != 0

    def test_megakernel_falls_back_when_traced(self):
        # The span ring appends at a global cursor the fused kernels
        # do not carry; traced worlds take the reference graph
        # (docs/megakernel.md, follow-ups).
        state, params, app = _phold()
        params = params.replace(megakernel=True)
        assert megakernel.enabled(state, params, app)
        traced = trace.ensure_lineage(state, rate=1.0)
        assert not megakernel.enabled(traced, params, app)


class TestStructuralCost:
    @pytest.mark.parametrize("fused", [False, True])
    def test_lineage_absent_graph_identical_and_zero_kernel_delta(
            self, fused):
        # lineage=None is a trace-time static: attach-then-detach
        # lowers to byte-identical HLO, so the kernelcount delta is
        # exactly 0 -- on the reference graph and the fused one.
        state, params, app = _lossy_bulk()
        params = params.replace(megakernel=fused, persistent=fused)
        txt = engine.run_until.lower(state, params, app, SEC).as_text()
        rt = trace.ensure_lineage(state).replace(lineage=None)
        txt_rt = engine.run_until.lower(rt, params, app, SEC).as_text()
        assert txt == txt_rt
        kc = _load_tool("kernelcount")
        assert kc.hlo_counts(txt) == kc.hlo_counts(txt_rt)
        traced = trace.ensure_lineage(state)
        txt_tr = engine.run_until.lower(traced, params, app, SEC).as_text()
        assert txt_tr != txt  # the tracer really traces in when present

    def test_shape_key_discriminates_lineage(self):
        state, params, app = _lossy_bulk()
        k0 = shapes.shape_key(state, params)
        k1 = shapes.shape_key(trace.ensure_lineage(state), params)
        assert k0 != k1
        # ...but the key does NOT fragment on the sampling rate
        # (rate_x1p32 is traced data, not a shape).
        k2 = shapes.shape_key(
            trace.ensure_lineage(state, rate=0.5), params)
        assert k1 == k2


class TestTrajectoryNeutrality:
    def _assert_neutral(self, bare, traced, label):
        assert traced.lineage is not None and bare.lineage is None
        la, ta = jax.tree_util.tree_flatten(bare)
        lb, tb = jax.tree_util.tree_flatten(traced.replace(lineage=None))
        assert ta == tb
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), label

    @pytest.mark.tier0
    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_phold_bitwise_neutral(self, rx_batch):
        state, params, app = _phold(rx_batch=rx_batch)
        params = params.replace(megakernel=False)
        bare = engine.run_chunked(state, params, app, 2 * SEC)
        traced = engine.run_chunked(
            trace.ensure_lineage(state, rate=0.5), params, app, 2 * SEC)
        self._assert_neutral(bare, traced,
                             f"phold rx_batch={rx_batch}")
        assert int(traced.lineage.n_assigned) > 0, "nothing sampled"

    def test_lossy_bulk_bitwise_neutral(self):
        state, params, app = _lossy_bulk()
        bare = engine.run_chunked(state, params, app, 4 * SEC)
        traced = engine.run_chunked(
            trace.ensure_lineage(state, rate=0.25), params, app, 4 * SEC)
        self._assert_neutral(bare, traced, "lossy bulk")
        assert int(traced.lineage.n_assigned) > 0

    def test_off_mesh_sharded_ring_raises(self):
        state, params, app = _lossy_bulk(num_hosts=8)
        bad = trace.ensure_lineage(state, shards=4)
        with pytest.raises(ValueError, match="outside a mesh"):
            engine.run_until(bad, params, app, SEC)


class TestMeshParity:
    """Single device vs 4-shard mesh on the conftest's 8 virtual CPU
    devices: the seeded sampler picks the SAME packets and the drains
    merge the SAME span multisets."""

    def _world(self, shards):
        state, params, app = _phold(rx_batch=1)
        state = trace.ensure_lineage(state, rate=0.5, shards=shards)
        return state, params, app

    @pytest.mark.parametrize("shards", [4, 8])
    def test_spans_match_single_vs_mesh(self, shards):
        t_end, step = 2 * SEC, SEC // 2
        st1, pr, app = self._world(shards=1)
        _o1, ld1 = _drain_chunked(
            st1, pr, app, t_end, step,
            lambda s, t: engine.run_chunked(s, pr, app, t))

        stm, prm, appm = self._world(shards=shards)
        mesh = make_mesh(jax.devices()[:shards])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _om, ldm = _drain_chunked(
                stm, prm, appm, t_end, step,
                lambda s, t: mesh_run_chunked(s, prm, appm, t, mesh=mesh))

        def multiset(rows):
            return sorted(tuple(sorted(r.items())) for r in rows)

        assert ld1.rows, "no spans drained"
        assert multiset(ld1.rows) == multiset(ldm.rows)
        s1, sm = ld1.summary(), ldm.summary()
        assert s1["n_assigned"] == sm["n_assigned"] > 0
        assert s1["ids_seen"] == sm["ids_seen"]
        assert s1["ids_delivered"] == sm["ids_delivered"]
        assert sm["shards"] == shards

    def test_mesh_shard_mismatch_raises(self):
        st, pr, app = self._world(shards=2)
        mesh = make_mesh(jax.devices()[:4])
        with pytest.raises(ValueError, match="ensure_lineage"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                mesh_run_chunked(st, pr, app, SEC, mesh=mesh)


class TestRingWrap:
    def test_wrap_keeps_exact_lifetime_counters(self):
        # A ring far too small for the run loses span rows (resolution)
        # but never counts: n_assigned and the append total are exact,
        # so surviving + lost always equals the unwrapped span count.
        state, params, app = _phold(rx_batch=1)
        params = params.replace(megakernel=False)
        _f, full = _drain_chunked(
            trace.ensure_lineage(state, rate=1.0),
            params, app, 2 * SEC, SEC // 2,
            lambda s, t: engine.run_chunked(s, params, app, t))
        _w, wrap = _drain_chunked(
            trace.ensure_lineage(state, rate=1.0, capacity=64),
            params, app, 2 * SEC, 2 * SEC,  # one launch: no mid-drains
            lambda s, t: engine.run_chunked(s, params, app, t))
        assert full.rows_lost == 0, "full ring should not wrap"
        assert wrap.rows_lost > 0, "tiny ring should wrap"
        assert wrap.n_assigned == full.n_assigned > 0
        assert len(wrap.rows) + wrap.rows_lost == len(full.rows)
        # Every surviving row is bitwise one of the full run's spans
        # (the wrap loses rows, it never corrupts them).
        from collections import Counter
        key = lambda r: (r["t"], r["id"], r["host"], r["stage"],
                         r["reason"])
        extra = Counter(map(key, wrap.rows)) - \
            Counter(map(key, full.rows))
        assert not extra, f"wrap invented spans: {extra}"


class TestNetemKillReasons:
    def _flap_world(self):
        state, params, app = _phold(msgs_per_host=4)
        tl = netem.timeline()
        tl.host_down(3, at=100 * MS)
        tl.link_down(1, 2, at=100 * MS).link_up(1, 2, at=SEC)
        state, params = netem.install(state, params, tl)
        return trace.ensure_lineage(state, rate=1.0), params, app

    def test_fatal_hops_name_the_netem_reason(self, tmp_path):
        state, params, app = self._flap_world()
        _out, ld = _drain_chunked(
            state, params, app, 2 * SEC, SEC // 2,
            lambda s, t: engine.run_chunked(s, params, app, t),
            spans_path=str(tmp_path / "spans.jsonl"))
        s = ld.summary()
        assert s["drops"].get("host_down", 0) > 0
        assert s["drops"].get("link_down", 0) > 0
        # tools/parse.py renders the kill reason on the fatal hop of
        # the dropped packet's chain.
        pa = _load_tool("parse")
        digest = pa.parse_spans(str(tmp_path))
        assert digest["drop_reasons"].get("host_down", 0) > 0
        assert any("[host_down]" in e["chain"] or
                   "[link_down]" in e["chain"]
                   for e in digest["dropped_examples"])


class TestParseAndPlot:
    def test_spans_digest_and_waterfall_render(self, tmp_path):
        state, params, app = _lossy_bulk()
        traced = trace.ensure_lineage(state, rate=0.5)
        _out, ld = _drain_chunked(
            traced, params, app, 8 * SEC, 2 * SEC,
            lambda s, t: engine.run_chunked(s, params, app, t),
            spans_path=str(tmp_path / "spans.jsonl"))
        assert ld.rows, "lossy bulk produced no spans"
        # Timestamps in the jsonl are the drain-merged sim-time order.
        ts = [json.loads(ln)["t"] for ln in
              (tmp_path / "spans.jsonl").read_text().splitlines()]
        assert ts and ts == sorted(ts)
        pa = _load_tool("parse")
        digest = pa.parse_spans(str(tmp_path))
        assert digest["spans"] == len(ld.rows)
        assert digest["ids_seen"] == ld.summary()["ids_seen"]
        assert digest["ids_delivered"] > 0
        for story in digest["slowest_deliveries"]:
            assert story["chain"].startswith("emit@h")
            assert story["latency_ns"] >= 0
        # parse_dir folds the digest into the data-directory summary.
        assert pa.parse_dir(str(tmp_path))["lineage"]["spans"] > 0
        pytest.importorskip("matplotlib")
        pl = _load_tool("plot")
        written = pl.main(str(tmp_path))
        p = tmp_path / "spans.png"
        assert str(p) in written
        assert p.exists() and p.stat().st_size > 0


class TestBenchdiffLineageGate:
    """benchdiff refuses to diff a traced run against an untraced one
    (or different rates) -- like the scope and flight-recorder gates."""

    BASE = {"metric": "phold_events_per_sec", "value": 1000.0,
            "wall_sec": 10.0,
            "config": {"lineage": None}}

    def _write(self, tmp_path, name, data):
        p = tmp_path / name
        p.write_text(json.dumps(data))
        return str(p)

    def test_lineage_config_mismatch_refused(self, tmp_path):
        new = json.loads(json.dumps(self.BASE))
        new["config"]["lineage"] = "0.01"
        bd = _load_tool("benchdiff")
        rc = bd.main([self._write(tmp_path, "old.json", self.BASE),
                      self._write(tmp_path, "new.json", new)])
        assert rc == 2

    def test_same_lineage_config_compares(self, tmp_path):
        old = json.loads(json.dumps(self.BASE))
        old["config"]["lineage"] = "1%"
        new = json.loads(json.dumps(old))
        new["value"] = 1010.0
        bd = _load_tool("benchdiff")
        rc = bd.main([self._write(tmp_path, "old.json", old),
                      self._write(tmp_path, "new.json", new)])
        assert rc == 0

    def test_legacy_unstamped_stays_comparable(self, tmp_path):
        old = json.loads(json.dumps(self.BASE))
        del old["config"]["lineage"]  # recorded before the stamp
        new = json.loads(json.dumps(self.BASE))
        bd = _load_tool("benchdiff")
        rc = bd.main([self._write(tmp_path, "old.json", old),
                      self._write(tmp_path, "new.json", new)])
        assert rc == 0


class TestReplayOnDemand:
    def test_replay_installs_lineage_after_the_fact(self, phold_ck,
                                                    tmp_path):
        # The record has NO lineage; the replay installs the tracer
        # after restoring the checkpoint, stays bitwise-verified
        # against the recorded windows, and writes spans.jsonl for the
        # replayed span (the seeded sampler picks the same packets the
        # original run would have traced).
        out = str(tmp_path / "re")
        summary = replay.replay(phold_ck, lineage="0.5", out_dir=out)
        assert summary["replay"]["windows_verified"] > 0
        ls = summary["lineage"]
        assert ls["n_assigned"] > 0 and ls["spans"] > 0
        rows = [json.loads(ln) for ln in
                open(os.path.join(out, "spans.jsonl"))]
        assert len(rows) == ls["spans"]

    def test_window_out_of_range_names_the_span(self, phold_ck):
        # Satellite: `replay --window K` beyond the record must say
        # what IS available instead of a bare KeyError (CLI rc 2).
        with pytest.raises(ValueError,
                           match="outside the recorded range"):
            replay.replay(phold_ck, window=99999)

    def test_run_stamps_and_drains_lineage(self, tmp_path):
        # sim.run(lineage=...) under checkpointing stamps run.json and
        # drains spans.jsonl alongside the record.
        d = str(tmp_path / "run")
        state, params, app = sim.build_phold(**KW)
        sim.run(state, params, app, lineage="0.5",
                checkpoint_every=EVERY, checkpoint_dir=d,
                checkpoint_world=("phold", KW))
        info = json.load(open(os.path.join(d, "ckpt", "run.json")))
        assert info["lineage"] == "0.5"
        rows = [json.loads(ln) for ln in
                open(os.path.join(d, "spans.jsonl"))]
        assert rows, "checkpointed lineage run drained no spans"


class TestFlightRows:
    def test_small_ring_wraps_and_replay_still_verifies(self, tmp_path):
        # Satellite: `--flight-rows N` sizes the telemetry ring.  A
        # ring smaller than the windows-per-checkpoint span WRAPS --
        # windows.jsonl keeps only each span's newest rows -- but the
        # loss is deterministic, so replay re-runs the same grid, loses
        # the same rows, and the bitwise verify still passes.
        d = str(tmp_path / "wrap")
        state, params, app = sim.build_phold(**KW)
        state = trace.ensure_flight_recorder(state, rows=4)
        assert state.fr.steps.shape[0] == 4
        sim.run(state, params, app, checkpoint_every=EVERY,
                checkpoint_dir=d, checkpoint_world=("phold", KW))
        rows = [json.loads(ln) for ln in
                open(os.path.join(d, "windows.jsonl"))]
        assert rows
        hi = max(r["window"] for r in rows)
        assert len(rows) < hi + 1, "ring never wrapped; shrink rows"
        summary = replay.replay(d)
        assert summary["replay"]["windows_verified"] > 0

    def test_rows_argument_validates(self):
        state, params, app = sim.build_phold(**KW)
        with pytest.raises(ValueError):
            trace.ensure_flight_recorder(state, rows=0)
