"""Statescope diff: first-divergence localization between two runs.

The reference debugging story for "two runs disagree" is printf
archaeology: re-run both with more logging and eyeball the logs until
something differs.  Here every run can carry a statescope digest block
(core/state.py DigestBlock, trace.ensure_digests): at the close of every
N-th window the device folds each state field-group -- pool, inbox,
socks, hosts, rng, netem, app -- into a 64-bit checksum per host-shard,
drained to digests.jsonl.  Digests are deterministic and bitwise
trajectory-neutral, and a mesh run's per-shard columns equal the
single-device run's, so two digest streams are directly comparable
across seeds, configs, device counts, and backends (megakernel on/off).

`diff_runs` is the comparison in three escalating stages:

  1. STREAM ALIGN -- index both digests.jsonl streams by global window,
     walk the common windows in order, and name the first divergent
     (window, field group, shard).  When the runs recorded different
     shard counts (mesh vs single device) the per-shard columns are
     wrap-summed first: the group checksum is a commutative i64 sum
     over elements, so the reduction is shard-layout-independent by
     construction.
  2. ANCHOR -- for checkpointed runs (--checkpoint-every), restore each
     run's nearest checkpoint at-or-before the last AGREEING window
     (replay.find_checkpoint + checkpoint.load on the rebuilt world
     template).
  3. RE-EXECUTE + LOCALIZE -- re-run both spans to the same sim time
     (the divergent window's recorded t_end; chunking is trajectory-
     invariant, so an off-grid target is safe for state comparison),
     gather both states to the host, and compare the divergent field
     group leaf-by-leaf, element-by-element: the report names the
     field, flat index, owning host, expected/got values, and -- for
     float leaves -- the absolute and ulp deltas.

Uncheckpointed digest runs stop after stage 1 with a note; the stream
report alone already names the window and field group.

Comparability is validated eagerly and by name (the replay --window
range-error pattern): a directory that is not a digest-recorded run, a
digest-cadence mismatch, a schema mismatch (checkpoint manifests stamp
the field-group schema version), or a --devices override that matches
neither run's recorded layout all raise DiffUsageError before any
device work.  Exit-code mapping lives in cli.diff_cmd: 0 agree,
1 diverged, 2 usage.

See docs/observability.md "Statescope"; tools/divergediff.py drives the
three comparison axes (run-vs-run, mesh-vs-single, backend-vs-backend).
"""

from __future__ import annotations

import json
import os

from .core.state import DIGEST_GROUPS, DIGEST_SCHEMA

_M64 = (1 << 64) - 1


class DiffUsageError(ValueError):
    """A user-facing diff failure: not a digest-recorded run, or two
    runs whose digest configs are incomparable (named in the message)."""


def _wrap_sum(vals) -> int:
    """Wrapping-i64 sum of a shard-column list: the reduction that maps
    a [D]-column digest row onto its single-shard value (the group
    checksum is a commutative mod-2^64 sum over elements)."""
    s = sum(int(v) for v in vals) & _M64
    return s - (1 << 64) if s >= (1 << 63) else s


def load_digests(data_dir: str) -> dict:
    """Load one run's digest record: rows from digests.jsonl plus the
    comparability stamps (cadence, shard count, schema, device count)
    from ckpt/run.json and the newest checkpoint manifest when the run
    was checkpointed.  Raises DiffUsageError when `data_dir` is not a
    digest-recorded run directory."""
    if not os.path.isdir(data_dir):
        raise DiffUsageError(
            f"{data_dir}: not a run data directory (expected the "
            f"--data-directory of a digest-recorded run)")
    path = os.path.join(data_dir, "digests.jsonl")
    if not os.path.exists(path):
        raise DiffUsageError(
            f"{path}: no digest record -- re-run with --digest-every N "
            f"(or sim.run(digest=N)) to make the run diffable")
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    if not rows:
        raise DiffUsageError(f"{path}: empty digest record")
    info = {}
    run_json = os.path.join(data_dir, "ckpt", "run.json")
    if os.path.exists(run_json):
        with open(run_json) as f:
            info = json.load(f)
    every = info.get("digest")
    if not every:
        # Uncheckpointed digest run: infer the cadence from the global
        # window stamps (rows record their window index, so the stream
        # itself carries the grid).
        every = (rows[1]["window"] - rows[0]["window"]
                 if len(rows) > 1 else None)
    schema = None
    ckpt_dir = os.path.join(data_dir, "ckpt")
    if os.path.isdir(ckpt_dir):
        from . import replay as replay_mod
        try:
            _, man = replay_mod.find_checkpoint(data_dir, None)
            schema = (man.get("digest") or {}).get("schema")
        except (FileNotFoundError, ValueError):
            pass
    shards = len(rows[0]["sums"][DIGEST_GROUPS[0]])
    # World-axis stamp: run.json for ensemble runs, else the rows
    # themselves (ensemble DigestDrains stamp a "world" column).
    # Missing on both means a legacy/solo record -- 1.
    n_worlds = int(info.get("n_worlds") or 0)
    if not n_worlds:
        n_worlds = len({r["world"] for r in rows if "world" in r}) or 1
    return {"dir": data_dir, "rows": rows, "every": every,
            "shards": shards, "schema": schema,
            "devices": info.get("devices"),
            "n_worlds": n_worlds,
            "checkpointed": os.path.exists(run_json)}


def _check_comparable(a: dict, b: dict, devices) -> None:
    """Named refusals for incomparable digest records -- eager, before
    any stream walk or device work."""
    for r in (a, b):
        if r.get("n_worlds", 1) != 1:
            raise DiffUsageError(
                f"{r['dir']}: digest record of a {r['n_worlds']}-world "
                f"ensemble run -- the stream interleaves per-world rows "
                f"and a pairwise diff would silently mix world axes; "
                f"summarize per world with `tools/parse.py ensemble` "
                f"(first-divergence-from-world-0 is computed there)")
    if a["every"] and b["every"] and int(a["every"]) != int(b["every"]):
        raise DiffUsageError(
            f"digest cadence mismatch: {a['dir']} recorded every "
            f"{a['every']} window(s), {b['dir']} every {b['every']} -- "
            f"the streams sample different windows and cannot be "
            f"aligned; re-run one side with --digest-every "
            f"{a['every']}")
    for r in (a, b):
        if r["schema"] is not None and int(r["schema"]) != DIGEST_SCHEMA:
            raise DiffUsageError(
                f"{r['dir']}: digest field-group schema "
                f"{r['schema']} does not match this build's schema "
                f"{DIGEST_SCHEMA} (core/state.py DIGEST_GROUPS "
                f"changed); re-record the run with this build")
    if devices is not None:
        for r in (a, b):
            orig = int(r["devices"] or 1)
            if r["checkpointed"] and int(devices) not in (orig, 1):
                raise DiffUsageError(
                    f"diff --devices {int(devices)}: {r['dir']} is a "
                    f"checkpoint of a {orig}-device run; it re-executes "
                    f"on the original mesh or gathers to 1 device, "
                    f"nothing in between (the shard layout is baked "
                    f"into the saved rings)")


def compare_streams(rows_a: list, rows_b: list) -> dict:
    """Stage 1: align two digest streams by global window and find the
    first divergent (window, group, shard).

    Returns {"divergence": None | {...}, "windows_compared": n,
    "last_agreeing_window": K | None, "notes": [...]}.  Shard columns
    are compared per-shard when both runs recorded the same count and
    wrap-sum-reduced otherwise (mesh-vs-single)."""
    by_a = {r["window"]: r for r in rows_a}
    by_b = {r["window"]: r for r in rows_b}
    common = sorted(set(by_a) & set(by_b))
    notes = []
    if not common:
        raise DiffUsageError(
            f"the digest streams share no windows (a: "
            f"{min(by_a)}..{max(by_a)}, b: {min(by_b)}..{max(by_b)}) "
            f"-- different cadences or disjoint spans")
    only_a = len(by_a) - len(common)
    only_b = len(by_b) - len(common)
    if only_a or only_b:
        notes.append(f"windows recorded by one run only: "
                     f"{only_a} in a, {only_b} in b (different stop "
                     f"times or ring wrap); compared the "
                     f"{len(common)} common windows")
    last_ok = None
    for w in common:
        ra, rb = by_a[w], by_b[w]
        if int(ra["t_end"]) != int(rb["t_end"]):
            # Same window index ending at different sim times: the
            # trajectories disagree about the window structure itself
            # (or the runs used different launch grids).  The window
            # boundary is part of the state evolution, so this IS the
            # divergence -- attribute it to the earliest group whose
            # checksum also differs, if any.
            notes.append(f"window {w}: t_end differs "
                         f"({int(ra['t_end'])} vs {int(rb['t_end'])})")
        for g in DIGEST_GROUPS:
            ca = [int(v) for v in ra["sums"][g]]
            cb = [int(v) for v in rb["sums"][g]]
            if len(ca) == len(cb):
                if ca != cb:
                    shard = next(i for i, (x, y) in
                                 enumerate(zip(ca, cb)) if x != y)
                    return {"divergence": {
                                "window": int(w),
                                "t_end": {"a": int(ra["t_end"]),
                                          "b": int(rb["t_end"])},
                                "group": g, "shard": shard},
                            "windows_compared": common.index(w) + 1,
                            "last_agreeing_window": last_ok,
                            "notes": notes}
            elif _wrap_sum(ca) != _wrap_sum(cb):
                return {"divergence": {
                            "window": int(w),
                            "t_end": {"a": int(ra["t_end"]),
                                      "b": int(rb["t_end"])},
                            "group": g, "shard": None},
                        "windows_compared": common.index(w) + 1,
                        "last_agreeing_window": last_ok,
                        "notes": notes}
        if int(ra["t_end"]) != int(rb["t_end"]):
            return {"divergence": {
                        "window": int(w),
                        "t_end": {"a": int(ra["t_end"]),
                                  "b": int(rb["t_end"])},
                        "group": None, "shard": None},
                    "windows_compared": common.index(w) + 1,
                    "last_agreeing_window": last_ok,
                    "notes": notes}
        last_ok = int(w)
    return {"divergence": None, "windows_compared": len(common),
            "last_agreeing_window": last_ok, "notes": notes}


# ---------------------------------------------------------------------------
# Stage 2/3: checkpoint-anchored re-execution and element localization.

def _group_fields(state) -> dict:
    """The digest field-groups as named (field, leaf) lists -- the
    human-facing twin of engine._digest_group_leaves (same leaves, same
    grouping, plus pytree path names for the report)."""
    import jax.tree_util as jtu

    out = {g: [] for g in DIGEST_GROUPS}

    def add(group, prefix, tree):
        if tree is None:
            return
        for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
            out[group].append((prefix + jtu.keystr(path), leaf))

    add("pool", "pool", state.pool)
    add("inbox", "inbox", state.inbox)
    add("socks", "socks", state.socks)
    for path, leaf in jtu.tree_flatten_with_path(state.hosts)[0]:
        name = "hosts" + jtu.keystr(path)
        g = "rng" if name.endswith((".rng_ctr", ".send_ctr")) else "hosts"
        out[g].append((name, leaf))
    add("netem", "nm", state.nm)
    # nm.killed is not digested (a per-shard partial under mesh, see
    # engine._digest_group_leaves), so it must not drive localization
    # either -- a mesh-vs-single re-execution pair can legitimately
    # disagree on the partial while every digested leaf matches.
    out["netem"] = [(n, l) for n, l in out["netem"]
                    if not n.endswith(".killed")]
    add("app", "app", state.app)
    return out


def _ulp_delta(a: float, b: float, bits: int) -> int:
    """Distance in representable floats between two same-width values:
    map the raw bit patterns onto the sign-magnitude-ordered integer
    line and subtract."""
    import numpy as np
    ui = np.uint32 if bits == 32 else np.uint64
    fi = np.float32 if bits == 32 else np.float64
    top = 1 << (bits - 1)

    def ordered(x):
        u = int(np.asarray(x, fi).view(ui))
        return (top - (u - top)) if u & top else (u + top)

    return abs(ordered(a) - ordered(b))


def _element_report(name, a, b, num_hosts, max_elements) -> dict | None:
    """Per-leaf comparison: None when bitwise equal, else the field's
    differing-element report (count, first `max_elements` elements with
    index / host / expected / got, float deltas)."""
    import numpy as np
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        raise DiffUsageError(
            f"field {name}: shapes differ ({a.dtype}{a.shape} vs "
            f"{b.dtype}{b.shape}) -- the runs have different world "
            f"configs and cannot be element-compared")
    fa, fb = a.reshape(-1), b.reshape(-1)
    if a.dtype.kind == "f":
        # Bitwise comparison (NaN == NaN, -0.0 != +0.0): the digest is
        # a function of the raw bits, so the localization must be too.
        ib = np.uint32 if a.dtype.itemsize == 4 else np.uint64
        neq = fa.view(ib) != fb.view(ib)
    else:
        neq = fa != fb
    idxs = np.flatnonzero(neq)
    if idxs.size == 0:
        return None
    n = int(a.shape[0]) if a.ndim else 1
    per_host = (a.size // num_hosts) if a.ndim and n % num_hosts == 0 \
        else None
    elements = []
    for i in idxs[:max_elements]:
        i = int(i)
        el = {"flat_index": i,
              "index": [int(x) for x in np.unravel_index(i, a.shape)]
              if a.ndim else [],
              "expected": _jsonable(fa[i]), "got": _jsonable(fb[i])}
        if per_host:
            el["host"] = i // per_host
        if a.dtype.kind == "f":
            el["abs_delta"] = abs(float(fa[i]) - float(fb[i]))
            el["ulp_delta"] = _ulp_delta(fa[i], fb[i],
                                         a.dtype.itemsize * 8)
        elements.append(el)
    return {"field": name, "dtype": str(a.dtype),
            "shape": list(a.shape), "elements_differing": int(idxs.size),
            "first": elements}


def _jsonable(v):
    import numpy as np
    v = np.asarray(v)
    if v.dtype.kind == "f":
        return float(v)
    return int(v)


def _reexec(data_dir: str, anchor_window: int, target_ns: int,
            devices=None):
    """Restore `data_dir`'s nearest checkpoint at-or-before
    `anchor_window` and re-execute to sim time `target_ns` on the
    original launch grid (capped at the target: off-grid stops are
    trajectory-invariant, engine.run_chunked).  Returns the host-side
    gathered state plus anchor metadata."""
    import jax

    from . import checkpoint as ckpt_mod
    from . import replay as replay_mod
    from .parallel.sharding import unshard

    info = replay_mod.load_run(data_dir)
    path, man = replay_mod.find_checkpoint(data_dir, anchor_window)
    n_orig = int(man.get("devices") or info.get("devices") or 1)
    exec_dev = n_orig if devices is None else int(devices)
    if exec_dev not in (n_orig, 1):
        raise DiffUsageError(
            f"diff --devices {exec_dev}: {data_dir} is a checkpoint of "
            f"a {n_orig}-device run; it re-executes on the original "
            f"mesh or gathers to 1 device, nothing in between")
    built = replay_mod.rebuild_world(info, data_dir,
                                     want_mesh=exec_dev > 1)
    state, params = ckpt_mod.load(path, built["state"], built["params"])
    app, mesh = built["app"], built["mesh"]
    if exec_dev == 1:
        mesh = None
    t = int(state.now)
    hb_ns, every_ns = info.get("hb_ns"), info.get("every_ns")
    stop = int(info["stop_ns"])
    while t < int(target_ns):
        t = min(replay_mod.next_sync(t, stop, hb_ns, every_ns),
                int(target_ns))
        if mesh is not None:
            from . import parallel
            state = parallel.mesh_run_chunked(state, params, app, t,
                                              mesh=mesh)
        else:
            from .core import engine
            state = engine.run_chunked(state, params, app, t)
    jax.block_until_ready(state)
    return {"state": unshard(state),
            "anchor": {"checkpoint": os.path.basename(path),
                       "window": int(man["window"]),
                       "t_ns": int(man["t_ns"]), "devices": exec_dev}}


def localize_elements(dir_a: str, dir_b: str, stream: dict, *,
                      devices=None, max_elements: int = 8) -> dict:
    """Stage 2+3: checkpoint-anchored element localization of a stream
    divergence.  Re-executes both runs from their last agreeing
    anchors to the divergent window's t_end and element-compares the
    divergent field group first, then every other group."""
    div = stream["divergence"]
    # Anchor at the DIVERGENT window, not the last agreeing one: a
    # checkpoint at window W holds the state at W's *start*, so the
    # nearest checkpoint at-or-before the divergent window still
    # predates that window's digest row -- and it is the newest anchor
    # that provably carries each run's own trajectory (including any
    # externally injected state the digests first noticed here).
    anchor_w = int(div["window"])
    # Both streams agreed on every window up to the anchor, so the two
    # t_end stamps agree there; for the divergent window itself they
    # may not -- compare at the earlier of the two (states at one sim
    # time are directly comparable; chunking is trajectory-invariant).
    target = min(int(div["t_end"]["a"]), int(div["t_end"]["b"]))
    a = _reexec(dir_a, anchor_w, target, devices=devices)
    b = _reexec(dir_b, anchor_w, target, devices=devices)
    return {"anchor": {"a": a["anchor"], "b": b["anchor"]},
            "target_ns": target,
            **compare_states(a["state"], b["state"], div["group"],
                             max_elements=max_elements)}


def compare_states(sa, sb, first_group=None, *,
                   max_elements: int = 8) -> dict:
    """Element-compare two host-side states of one world at one sim
    time, digest field group by group (`first_group` first): the groups
    that differ and each differing field's element report."""
    h = int(sa.hosts.num_hosts)
    if int(sb.hosts.num_hosts) != h:
        raise DiffUsageError(
            f"the runs have different (padded) host counts "
            f"({h} vs {int(sb.hosts.num_hosts)}) and cannot be "
            f"element-compared; pad both to the same layout")
    ga, gb = _group_fields(sa), _group_fields(sb)
    # The stream names the divergent group; element-compare it first so
    # the report leads with the cause, then sweep the rest (a single
    # root divergence usually fans out into several groups by the end
    # of the window).
    order = list(DIGEST_GROUPS)
    if first_group in order:
        order.remove(first_group)
        order.insert(0, first_group)
    fields = []
    groups_differing = []
    for g in order:
        hit = False
        for (name, la), (_, lb) in zip(ga[g], gb[g]):
            rep = _element_report(name, la, lb, h, max_elements)
            if rep is not None:
                rep["group"] = g
                fields.append(rep)
                hit = True
        if hit:
            groups_differing.append(g)
    return {"groups_differing": groups_differing, "fields": fields}


def diff_runs(dir_a: str, dir_b: str, *, localize: bool = True,
              devices=None, max_elements: int = 8,
              quiet: bool = True) -> dict:
    """Compare two digest-recorded runs; returns the report dict.

    `localize=False` stops at the stream comparison (stage 1).  Raises
    DiffUsageError for non-runs or incomparable digest configs."""
    a = load_digests(dir_a)
    b = load_digests(dir_b)
    _check_comparable(a, b, devices)
    stream = compare_streams(a["rows"], b["rows"])
    report = {
        "runs": {"a": dir_a, "b": dir_b},
        "every": a["every"] or b["every"],
        "shards": {"a": a["shards"], "b": b["shards"]},
        "windows_compared": stream["windows_compared"],
        "last_agreeing_window": stream["last_agreeing_window"],
        "divergence": stream["divergence"],
        "localization": None,
        "notes": list(stream["notes"]),
    }
    if stream["divergence"] is None:
        return report
    if not localize:
        report["notes"].append("localization skipped (--no-localize)")
        return report
    if not (a["checkpointed"] and b["checkpointed"]):
        missing = [r["dir"] for r in (a, b) if not r["checkpointed"]]
        report["notes"].append(
            f"element localization needs checkpointed runs; "
            f"{' and '.join(missing)} recorded no checkpoints "
            f"(re-run with --checkpoint-every)")
        return report
    if not quiet:
        import sys
        d = stream["divergence"]
        print(f"[shadow1-tpu] diff: digest streams diverge at window "
              f"{d['window']} (group {d['group']}, shard {d['shard']}); "
              f"re-executing both spans to localize", file=sys.stderr)
    report["localization"] = localize_elements(
        dir_a, dir_b, stream, devices=devices,
        max_elements=max_elements)
    return report


def format_report(report: dict) -> str:
    """The human-readable diff report (the --json flag prints the dict
    instead)."""
    lines = []
    div = report["divergence"]
    if div is None:
        lines.append(
            f"no divergence: {report['windows_compared']} digest "
            f"window(s) agree across every field group "
            f"(a: {report['runs']['a']}, b: {report['runs']['b']})")
    else:
        shard = "" if div["shard"] is None else f", shard {div['shard']}"
        lines.append(
            f"DIVERGED at window {div['window']}: field group "
            f"'{div['group']}'{shard} "
            f"(last agreeing window: {report['last_agreeing_window']})")
    loc = report.get("localization")
    if loc:
        aa, ab = loc["anchor"]["a"], loc["anchor"]["b"]
        lines.append(
            f"  re-executed from {aa['checkpoint']} (window "
            f"{aa['window']}) / {ab['checkpoint']} (window "
            f"{ab['window']}) to t={loc['target_ns']} ns")
        lines.append(f"  field groups differing: "
                     f"{', '.join(loc['groups_differing'])}")
        for f in loc["fields"]:
            lines.append(
                f"  {f['field']} [{f['group']}] {f['dtype']}"
                f"{tuple(f['shape'])}: {f['elements_differing']} "
                f"element(s) differ")
            for el in f["first"]:
                host = f" host {el['host']}" if "host" in el else ""
                delta = ""
                if "ulp_delta" in el:
                    delta = (f" (abs {el['abs_delta']:g}, "
                             f"{el['ulp_delta']} ulp)")
                lines.append(
                    f"    [{','.join(str(i) for i in el['index'])}]"
                    f"{host}: expected {el['expected']}, got "
                    f"{el['got']}{delta}")
    for note in report.get("notes", []):
        lines.append(f"  note: {note}")
    return "\n".join(lines)
