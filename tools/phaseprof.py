"""Device time of the window loop per phase, from a profiler trace.

Every op the engine compiles carries its phase in its `op_name`
metadata: the innermost name of `shadow1_tpu.trace.PHASES` (exchange,
scan, bounds, close, rx, tcp_timers, app, tcp_tx, stage, tx, cpu,
mesh_min) on its name stack.  This tool runs one of its worlds for a few
launches under `jax.profiler.trace`, then reduces the device's op events
to self time per phase, and prints what no phase covers:

* `unscoped`: ops of the window loop no phase covers: the loops' own
  `while` ops, ops in computations that mix phases;
* `other`: ops of no program in the map (transfers, small programs).

    python tools/phaseprof.py --world phold --hosts 16384
    python tools/phaseprof.py --world onion --circuits 2000 --warm-ms 300
    python tools/phaseprof.py --world phold --hosts 65536 --devices 4

An op's phase comes from the compiled program's HLO text
(`hlo_phases`): the name stack in its `metadata={op_name=...}`, or, for
an op XLA made while compiling (no `op_name`: a sort's expansion, a
copy), the one phase the ops it fuses, or the ops of its computation,
share.  The trace's op events are matched to it by HLO name (the
`%fusion.12 = ...` line a TPU's `XLA Ops` event is named by, the CPU's
`hlo_op` stat).  A reduction of a trace that has no such map can read
a TPU op's name stack from the `tf_op` stat of the op's event metadata,
but that stat is missing for the ops XLA made (8-14% of busy time).
An executable loaded from a compile cache that an older build wrote
carries that build's name stacks: the cache key leaves metadata out
(jax's `jax_compilation_cache_include_metadata_in_key`).
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import shadow1_tpu  # noqa: E402,F401  (x64)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from shadow1_tpu import sim, trace  # noqa: E402
from shadow1_tpu.core import emit, engine, simtime  # noqa: E402

I32, I64 = jnp.int32, jnp.int64
SEC = simtime.SIMTIME_ONE_SECOND
MS = simtime.SIMTIME_ONE_MILLISECOND

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)$")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def hlo_phases(hlo_text):
    """{HLO instruction: phase or None} of a compiled program.  An
    instruction's phase is the innermost PHASES name on its `op_name`.
    One with no `op_name` at all -- XLA made it while compiling: a sort's
    expansion, a copy, a fusion rooted in such an op -- takes the one
    phase the named instructions it fuses share, else that of the
    nearest named instructions that consume its result (the most common
    one among the nearest).  What is left (the loops' own `while` ops)
    is None."""
    own, calls, users, members = {}, {}, {}, {}
    named = set()
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(1)
            members[cur] = []
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            named.add(name)
        own[name] = phase_of(op.group(1)) if op else None
        members[cur].append(name)
        c = _CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
        for ref in _REF.findall(rest.split(", metadata=", 1)[0]):
            users.setdefault(ref, []).append(name)

    def fused(name):
        found = {own[n] for n in members.get(calls.get(name), ())
                 if n in named}
        return found.pop() if len(found) == 1 else None

    def consumers(name):
        seen, level = {name}, users.get(name, [])
        while level:
            found = collections.Counter(own[u] for u in level
                                        if u in named and own[u])
            if found:
                return found.most_common(1)[0][0]
            seen.update(level)
            level = [u for n in level if n not in named
                     for u in users.get(n, []) if u not in seen]
        return None

    return {n: own[n] if n in named else fused(n) or consumers(n)
            for n in own}


def phase_of(op_name):
    """The innermost PHASES name on an op_name's name stack, or None."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in trace.PHASES:
            return part
    return None


def load_ops(trace_dir):
    """{device: [(HLO op, start_ns, end_ns)]}: the op events of the
    newest `.xplane.pb` under a `jax.profiler.trace` dir.  A device is a
    `/device:*` plane's `XLA Ops` line, whose events are named by their
    HLO line ("%fusion.12 = s32[...] ..."); on the CPU it is the host
    plane's events that carry an `hlo_op` stat."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(files, key=os.path.getmtime), "rb") as f:
        raw = f.read()
    out = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name != "XLA Ops":
                continue
            for e in line.events:
                if on_device:
                    out.setdefault(plane.name, []).append(
                        (e.name.split(" = ", 1)[0].lstrip("%"),
                         e.start_ns, e.end_ns))
                    continue
                hlo_op = dict(e.stats).get("hlo_op")
                if hlo_op is not None:
                    out.setdefault("cpu", []).append(
                        (hlo_op, e.start_ns, e.end_ns))
    return out


def self_times(ops):
    """[(op, self_ns)]: each op's duration less that of the ops nested
    in it (a `while` spans its body's ops on the op line)."""
    evs = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [b - a for _n, a, b in evs]
    stack = []
    for i, (_n, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(evs[i], own[i]) for i in range(len(evs))]


def phase_table(ops_by_device, phases):
    """{device: {phase|"unscoped"|"other": self seconds}} of load_ops'
    events, each op under its phase in the program's `hlo_phases` map:
    "unscoped" where the map names none, "other" for an op of no program
    in the map."""
    table = {}
    for dev, ops in sorted(ops_by_device.items()):
        row = {}
        for (op, _a, _b), ns in self_times(ops):
            key = (phases[op] or "unscoped") if op in phases else "other"
            row[key] = row.get(key, 0.0) + ns / 1e9
        table[dev] = row
    return table


def format_table(table, steps=None):
    lines = []
    for dev, row in table.items():
        busy = sum(row.values())
        lines.append(f"{dev}: busy {busy:.6f} s"
                     + (f", {steps} micro-steps" if steps else ""))
        for key in (*trace.PHASES, "unscoped", "other"):
            if key in row:
                per = (f"  {row[key] / steps * 1e6:10.1f} us/step"
                       if steps else "")
                lines.append(f"  {key:<11s} {row[key]:12.6f} s "
                             f"{100 * row[key] / busy:6.2f}%{per}")
    return "\n".join(lines)


def _build(args):
    if args.world == "phold":
        state, params, app = sim.build_phold(
            num_hosts=args.hosts, msgs_per_host=4,
            mean_delay_ns=10 * MS, stop_time=10 * SEC,
            pool_capacity=args.hosts * 8, rx_batch=2)
        warm_t = args.warm_ms * MS
    else:
        state, params, app = sim.build_onion(
            num_circuits=args.circuits, bytes_per_circuit=1 << 20,
            pool_slab=64, stop_time=120 * SEC)
        warm_t = args.warm_ms * MS
    state, params = jax.device_put((state, params), jax.devices()[0])
    return state, params, app, warm_t


def _compiled_text(state, params, app, t, devices):
    """The HLO text of the program `sim.run` launches for this world."""
    if devices <= 1:
        return engine.run_until.lower(state, params, app, t).compile() \
            .as_text()
    from shadow1_tpu import parallel
    from shadow1_tpu.parallel import mesh as mesh_mod
    mesh = parallel.make_mesh(jax.devices()[:devices])
    state, params = parallel.pad_world_to_mesh(state, params, devices)
    sspecs = mesh_mod._state_specs(state)
    pspecs = mesh_mod._param_specs(params)
    fn = mesh_mod._build(app, mesh, sspecs, pspecs)
    state, params = mesh_mod._place(mesh, (state, params), (sspecs, pspecs))
    with mesh:
        return fn.lower(state, params, jnp.asarray(t, I64)).compile() \
            .as_text()


def profile_world(args):
    """Warm to `warm_ms`, then trace `launches` launches of `chunk_ms`;
    returns (table, micro-steps in the traced launches)."""
    state, params, app, t = _build(args)
    kw = {"devices": args.devices} if args.devices > 1 else {}
    state = jax.block_until_ready(sim.run(state, params, app, until=t,
                                          **kw))
    print(f"world={args.world} hosts={state.hosts.num_hosts} "
          f"devices={args.devices} warm to {t / SEC:g} sim-s",
          file=sys.stderr)
    phases = hlo_phases(_compiled_text(state, params, app,
                                       t + args.chunk_ms * MS,
                                       args.devices))
    steps0 = int(state.n_steps)
    trace_dir = tempfile.mkdtemp(prefix="phaseprof-")
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for _ in range(args.launches):
            t += args.chunk_ms * MS
            state = jax.block_until_ready(
                sim.run(state, params, app, until=t, **kw))
    print(f"{args.launches} launches in {time.perf_counter() - t0:.3f} s "
          f"(traced)", file=sys.stderr)
    return (phase_table(load_ops(trace_dir), phases),
            int(state.n_steps) - steps0)


def timeloop(name, state0, params, app, body, iters_pair=(50, 200),
             trials=3, quiet=False):
    """Slope-time `body` (state, t_h) -> (state, t_h): ms per iteration
    from the (iters_pair[1] - iters_pair[0]) wall-time difference."""
    res = {}
    for iters in iters_pair:
        def run(st, th):
            def cond(c):
                return c[0] < iters

            def b(c):
                i, s, t = c
                s, t = body(s, t)
                return i + 1, s, t

            return jax.lax.while_loop(cond, b,
                                      (jnp.asarray(0, I32), st, th))

        jf = jax.jit(run)
        th0, _ = engine._scan_all(state0, params, app)
        out = jf(state0, th0)
        jax.block_until_ready(out[1].now)
        ts = []
        for trial in range(trials):
            st2 = state0.replace(now=state0.now + trial)
            t0 = time.perf_counter()
            out = jf(st2, th0)
            jax.block_until_ready(out[1].now)
            ts.append(time.perf_counter() - t0)
        res[iters] = min(ts)
    slope = (res[iters_pair[1]] - res[iters_pair[0]]) \
        / (iters_pair[1] - iters_pair[0]) * 1e3
    if not quiet:
        print(f"{name:44s} {slope:8.3f} ms/iter", flush=True)
    return slope


def measure_staging_ms(state, params, app, iters_pair=(20, 60)) -> float:
    """ms per staging merge on the live backend: a forced loop of
    `_stage_emissions` over a fully-valid synthetic emissions buffer,
    slope-timed.  The merge's cost is shape-bound (one-hot masked
    selects over [H, E, Ko, C]), not data-bound, so the synthetic
    buffer measures the real phase; bench.py records the result as
    `profile.stage_emissions_ms` each round."""
    h = int(state.hosts.num_hosts)
    em = emit.empty(h, emit.SLOT_APP + 1, cols=state.pool.blk.shape[1])
    dst = (jnp.arange(h, dtype=I32) + 1) % h
    em = emit.put(em, jnp.ones((h,), jnp.bool_), emit.SLOT_APP,
                  dst=dst, sport=9, dport=9, proto=17, length=100)
    active = jnp.ones((h,), jnp.bool_)

    def body(s, th):
        s2, _placed = engine._stage_emissions(s, params, em, th, active,
                                              app)
        return s2, th + 1

    return timeloop("staging (forced)", state, params, app, body,
                    iters_pair=iters_pair, quiet=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--world", choices=("phold", "onion"), default="phold")
    ap.add_argument("--hosts", type=int, default=16384,
                    help="phold world size")
    ap.add_argument("--circuits", type=int, default=2000,
                    help="onion world size (hosts = 5 x circuits)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the world over this many devices")
    ap.add_argument("--warm-ms", type=int, default=250,
                    help="sim-ms to advance before tracing")
    ap.add_argument("--chunk-ms", type=int, default=250,
                    help="sim-ms a traced launch covers")
    ap.add_argument("--launches", type=int, default=2,
                    help="launches to trace")
    ap.add_argument("--json", action="store_true",
                    help="print the table as JSON")
    args = ap.parse_args(argv)
    table, steps = profile_world(args)
    if args.json:
        print(json.dumps({"table": table, "steps": steps}))
    else:
        print(format_table(table, steps))


if __name__ == "__main__":
    main()
