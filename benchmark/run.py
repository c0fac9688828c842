"""Run one benchmark cell once, on the chips it names, and print its result.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name from BENCHMARK.json: `benchmark/configs/<config>.json`,
`benchmark/traffic/<traffic>.json`, `benchmark/metrics/<metric>.py`, and
the configuration's plain reference `benchmark/reference/<name>.py`.

A run:

1. set-up (`setup_s`): imports and the runtime's start (span `runtime`),
   the world built from the seed (span `build`), and one warm launch of
   the first chunk from the world's initial state (span `warm`), which
   loads the executable from the compile cache in `.jax_cache/` at the
   checkout's root, or compiles it;
2. the window, from the initial state again: `sim.run(state, params, app,
   until=t)` on targets `chunk_sim_s` apart, blocking on each launch
   (spans `launch`, `block`), until `--seconds` of wall time have passed.
   A mix with a `pass_sim_s` restarts from the initial state at each
   pass's end (span `restart`), and the window holds whole passes only:
   it ends at the first pass end after `--seconds`.  `sim_s_per_s` is the
   simulated seconds advanced over the window's wall seconds;
3. with `--trace 1`, a `jax.profiler` trace of `trace_launches` launches
   of the window, after its first `trace_skip`, reduced to the per-layer
   metrics, the device's busy time and a breakdown (benchmark/tracing.py);
4. the check: after the window, the answers the program reports are
   compared with the configuration's plain reference; each number is
   printed beside its limit.

The last line of standard output is one JSON object; the checks are its
last key and the last lines of standard error.  Without a TPU, or with
fewer chips than the cell names, the run exits nonzero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SEC = 10**9


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


def load_json(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(rel):
    path = os.path.join(ROOT, rel)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "bench_" + rel.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, workload):
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(entry["file"])
    if int(config["chips"]) != int(cell["chips"]):
        raise ValueError(f"{workload}: the cell asks for {cell['chips']} "
                         f"chips, its configuration for {config['chips']}")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {
        "cell": cell,
        "config": config,
        "traffic": load_json(f"benchmark/traffic/{cell['traffic']}.json"),
        "reference": load_module(
            f"benchmark/reference/{config['reference']}.py"),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "readers": {m["name"]: load_module(f"benchmark/metrics/{m['name']}.py")
                    for m in per_layer},
    }


def ns(sim_s):
    return int(round(float(sim_s) * SEC))


def find_chips(chips, peaks):
    """The devices to report on; raises NoChip where the machine has no
    TPU or too few chips, and KeyError for a chip missing from peaks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU here: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips needed, {len(devs)} found")
    if devs[0].device_kind not in peaks["devices"]:
        raise KeyError(f"device kind {devs[0].device_kind!r} is not in "
                       f"benchmark/peaks.json")
    return devs


def _answers(state, fields):
    """{name: device array} of state fields; a path ending in "()" calls
    a view of the state."""
    out = {}
    for name, path in fields.items():
        obj = state
        for part in path.split("."):
            obj = getattr(obj, part.removesuffix("()"))
            if part.endswith("()"):
                obj = obj()
        out[name] = obj
    return out


def _plain(fields):
    return {k: v for k, v in fields.items() if not v.endswith("()")}


def _peak_bytes(devs):
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(bench, workload, seed, seconds, trace=False, *,
             require_chip=True, overrides=None, plant=None, program_kw=None):
    """Run a cell once; returns (result dict, check lines).

    `require_chip=False`, `overrides` (builder arguments, for small
    sizes), `plant` (a function of (state, params, app) that returns
    them) and `program_kw` (a function of the builder arguments that
    gives the program's alone), both to break the timed path, serve the
    benchmark's own tests and readings."""
    import jax

    from shadow1_tpu import sim

    import tracing
    import world

    r = resolve(bench, workload)
    cell, config, traffic = r["cell"], r["config"], r["traffic"]
    chips = int(cell["chips"])
    peaks = load_json("benchmark/peaks.json")
    if require_chip:
        devs = find_chips(chips, peaks)
    else:
        devs = jax.devices()
    used = devs[:chips]
    spans = {"runtime": time.perf_counter() - T_START}
    run_kw = {"devices": chips} if chips > 1 else {}
    chunk = ns(traffic["chunk_sim_s"])
    span = ns(traffic["pass_sim_s"]) if traffic.get("pass_sim_s") else 0
    if span and span % chunk:
        raise ValueError(f"{workload}: pass_sim_s is not a whole number of "
                         f"chunks")
    annot = jax.profiler.TraceAnnotation

    # -- set-up -----------------------------------------------------------
    t0 = time.perf_counter()
    with annot("build"):
        state, params, app, inputs = world.build(config, traffic, seed,
                                                 overrides, program_kw)
        if plant is not None:
            state, params, app = plant(state, params, app)
        jax.block_until_ready(state)
    spans["build"] = time.perf_counter() - t0
    init = state
    t0 = time.perf_counter()
    with annot("warm"):
        warm = jax.block_until_ready(
            sim.run(init, params, app, until=chunk, **run_kw))
    spans["warm"] = time.perf_counter() - t0
    launches = [(chunk, warm.now)]
    del warm
    steps = []                      # per window launch: (before, after)
    passes = []                     # pass-end answers, plain leaves
    t = 0
    skip = int(traffic.get("trace_skip", 0)) if trace else 0
    n_traced = int(traffic.get("trace_launches", 2)) if trace else 0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.perf_counter() - T_START

    # -- the window: from the initial state, whole passes only ------------
    w_start = time.perf_counter()
    n_launch = 0
    marks = [w_start]                # host clock after each launch's block
    while True:
        if span and t == span:
            with annot("restart"):
                passes.append(_answers(state, _plain(r["reference"].FIELDS)))
                state, t = init, 0
        if trace and n_launch == skip:
            jax.profiler.start_trace(trace_dir)
        before = state.n_steps
        t += chunk
        with annot("launch"):
            state = sim.run(state, params, app, until=t, **run_kw)
        with annot("block"):
            jax.block_until_ready(state)
        marks.append(time.perf_counter())
        launches.append((t, state.now))
        steps.append((before, state.n_steps))
        n_launch += 1
        if trace and n_launch == skip + n_traced:
            jax.profiler.stop_trace()
        if (time.perf_counter() - w_start >= seconds
                and (not span or t == span)
                and n_launch >= skip + n_traced):
            break
    wall = time.perf_counter() - w_start
    memory_peak = _peak_bytes(used)

    passes.append(_answers(state, r["reference"].FIELDS))
    with annot("fetch"):
        finals = jax.device_get(passes)
        launches = [(tgt, int(now)) for tgt, now in jax.device_get(launches)]
        steps = [int(b) - int(a) for a, b in jax.device_get(steps)]
    del state, init, passes
    sim_s = n_launch * chunk / SEC

    # -- the check against the plain reference ----------------------------
    kw = world.builder_kwargs(config, traffic, seed, overrides)
    checks, attempted, failed = r["reference"].check(
        kw, inputs, launches, finals, config["allowed_err_bits"])
    limits = r["reference"].LIMITS
    correct = failed == 0 and all(checks[k] <= limits[k] for k in limits)
    lines = [f"check {k} {checks[k]} limit {limits[k]}" for k in limits]

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        values = {"sim_s_per_s": sim_s / wall, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in r["end_to_end"]}
    else:
        devices, host_spans = tracing.load_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        devices = {d: ops for d, ops in devices.items()
                   if tracing.device_index(d) < chips}
        tl = [s for s in host_spans if s[0] == "launch"]
        tb = [s for s in host_spans if s[0] == "block"]
        if not tl or not tb or not devices:
            raise RuntimeError("the trace holds no launch or no device op")
        s0, s1 = min(s[1] for s in tl), max(s[2] for s in tb)
        red = tracing.reduce(devices, host_spans, s0, s1)
        rec = {"trace": red, "spans": spans, "sim_s_window": sim_s,
               "steps_slice": sum(steps[skip:skip + n_traced]),
               "steps_window": sum(steps)}
        metrics = {}
        for m in r["per_layer"]:
            v = r["readers"][m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = (sum(red["busy_ns"].values())
                            / len(red["busy_ns"]) / SEC)
        device["window_s"] = red["window_ns"] / SEC
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["device"] = device
    per = sorted(b - a for a, b in zip(marks, marks[1:]))
    result["spans"] = spans
    result["window"] = {"wall_s": wall, "sim_s": sim_s, "launches": n_launch,
                        "launch_s_min": per[0],
                        "launch_s_median": per[len(per) // 2],
                        "launch_s_max": per[-1]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result, lines


def prepare_env():
    """Before JAX starts: the compile cache stays inside the checkout, at
    one fixed path, and the host CPU backend is kept beside the TPU,
    since worlds are assembled on it (shadow1_tpu.build_on_host)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_env()
    sys.path[:0] = [BENCH, ROOT]
    bench = load_json("BENCHMARK.json")
    try:
        result, lines = run_cell(bench, args.workload, args.seed,
                                 args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"benchmark: {e}; refusing to run", file=sys.stderr)
        return 2
    for key in ("spans", "window"):
        print(key + " " + " ".join(f"{k} {v}" for k, v in result[key].items()),
              file=sys.stderr)
    for ln in lines:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
