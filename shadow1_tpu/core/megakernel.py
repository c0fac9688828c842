"""Fused Pallas micro-step: the window loop's phase graph as two kernels.

The reference micro-step (engine._microstep_core) traces ~5k HLO ops and
XLA's fusion boundaries roughly double every shared subexpression that
crosses them, so at small worlds the step is KERNEL-COUNT bound, not
data bound (PERF.md rounds 4-8; "Event Tensor" makes the same case for
dynamic event graphs).  This module packages the phase graph into two
hand-fused Pallas kernels over per-host slab blocks:

* K_DELIVER -- event drain + transport delivery: the whole `_rx_phase`
  (router enqueue, NIC rx tokens + CoDel, UDP/TCP arrival processing)
  for a block of hosts.
* K_TRANSPORT -- TCP transmit, emission staging (`_stage_emissions`,
  including routing + loopback), the parked-TX drain, virtual-CPU
  accounting, and the post-step per-host scan (`_scan_all` semantics),
  so the inner while body needs no separate re-scan.

Between the kernels run the phases the kernels must not carry: TCP
timers (already diet-gated) and the application tick.  The tick stays
outside even when an app's tick is provably row-local, because bitwise
equality forbids moving f32 TRANSCENDENTALS between compilation
contexts: XLA CPU compiles e.g. phold's log1p delay draw to ulp-
different results inside the interpret-mode kernel body than in the
main graph (measured -- jit vs eager of the identical reference window
loop already disagree by 1-2ns per draw).  Integer math is context-
stable, which is why every phase inside the kernels below is safe: the
f32 the kernels do touch (loss/reliability comparisons) is linear
arithmetic on rng bits, not transcendental expansions.

Blocking contract: every phase inside the kernels is ROW-LOCAL over
hosts -- per-host slab reductions, one-hot merges, row-local allocation.
The only cross-row inputs are read-only replicated tables (route_blk,
host_vertex, the netem overlay, seed_key), which every block reads
whole, and the only cross-row outputs are integer accumulators (event
count, error bitmask, netem kill count) which the kernels emit as
per-block partials merged outside (integer sum/OR are associative, so
the merge is bitwise-exact against the reference reduction).

The kernel bodies CALL the reference implementations on the blocked
state: `shadow1_tpu.core.engine` remains the single source of semantic
truth, and the fused path is bitwise-identical to the reference path by
construction (tests/test_megakernel.py asserts full-pytree equality).
Global host identity inside a block comes from the `hoff` mechanism the
mesh already uses: block b of a shard at offset `base` runs with
hoff = base + b * block_hosts, so RNG keys, packet SRC columns, and
host_vertex slicing see global ids.

The path is off by default (params.megakernel=False): on a TPU the
kernels do not lower through Mosaic yet (FusedPathUnavailable says why),
so asking for them there raises at trace time, and off the TPU they run
in Pallas interpret mode, which is how the CPU tests pin them bitwise
against the reference (`docs/megakernel.md` has the full contract).  The
flag is static (params.megakernel, in ShapeKey), so buckets never mix
fused and reference graphs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import emit, engine
from .state import I32, I64, ICOLS, STAGE_IN_FLIGHT, STAGE_TX_QUEUED, SimState

INV = engine.INV

# Per-host NetParams leaves: sliced to local rows under the mesh
# (parallel/mesh.py _PARAM_LOCAL) and blocked per kernel invocation.
_PARAMS_LOCAL = ("bw_up_Bps", "bw_down_Bps", "cpu_ns_per_event",
                 "autotune_snd", "autotune_rcv", "iface_buf_pkts",
                 "pcap_mask")
# Replicated leaves: global tables + scalars, read whole by every block.
_PARAMS_REP = ("route_blk", "host_vertex", "min_latency_ns", "seed_key",
               "stop_time", "bootstrap_end", "cpu_threshold_ns",
               "cpu_precision_ns", "qdisc")


class FusedPathUnavailable(RuntimeError):
    """The fused kernels were asked for on a backend whose Pallas
    lowering refuses them (the TPU's Mosaic compiler)."""


_MOSAIC_REFUSAL = (
    "params.megakernel=True asks for the fused Pallas kernels, and the "
    "TPU's Mosaic compiler refuses every one of them: K_WINDOW with "
    "'Only arrays with 32-bit element types can be converted to scalars, "
    "but got: float64' (kernel bodies carry int64 simulated time and "
    "float64 values; Mosaic lowers 32-bit element types only, and a "
    "kernel on an int64 block is UNIMPLEMENTED), K_DELIVER/K_TRANSPORT "
    "for rank-1 host blocks that are not a multiple of the 128-lane "
    "tiling, and the exchange kernel with a RecursionError in Mosaic's "
    "convert_element_type lowering (its core also sorts, which has no "
    "Pallas TPU lowering).  Run with megakernel=False (the default): the "
    "reference XLA graph compiles and runs on the chip "
    "(docs/megakernel.md, 'On the TPU').")


def enabled(state: SimState, params, app) -> bool:
    """Trace-time static: does this world take the fused path?  The
    log/capture rings and the lineage span ring append at global cursors
    (cross-row state the kernels do not carry), so those worlds fall
    back to the reference graph.  Every OTHER instrumentation block --
    flowscope sampling, statescope digests, the sentinel, the flight
    recorder, trace counters -- is window-close bookkeeping outside the
    micro-step loop and deliberately does NOT gate: --scope and
    --digest-every worlds keep the fused (and persistent) op diet,
    pinned bitwise by tests/test_megakernel.py's instrumented-world
    battery (docs/megakernel.md, "What gates and what doesn't")."""
    if not getattr(params, "megakernel", False):
        return False
    if not _interpret():
        raise FusedPathUnavailable(_MOSAIC_REFUSAL)
    return state.log is None and state.cap is None \
        and state.lineage is None


def persistent_enabled(state: SimState, params, app) -> bool:
    """Trace-time static: does this world run whole windows through the
    persistent K_WINDOW region (window_fused)?  Requires the megakernel
    path to be admissible at all, the params.persistent static, and an
    off-mesh world: the mesh's loop predicates and exchange are
    collectives (pmin/all_to_all), which cannot live inside a kernel, so
    sharded runs keep the per-phase fused kernels per shard."""
    if not enabled(state, params, app):
        return False
    if not getattr(params, "persistent", False):
        return False
    return state.hoff is None


def _interpret() -> bool:
    """Interpret mode everywhere but the TPU, where enabled() refuses the
    fused path before any kernel is traced: no kernel ever runs
    interpreted on the chip."""
    return jax.default_backend() != "tpu"


def _grid(h: int) -> int:
    """Blocks per kernel launch.  Grid 1 degenerates to the reference
    fusion behavior (XLA unrolls single-trip loops), so prefer the
    largest small divisor; odd host counts fall back to 1 (correct,
    just without the op-count win)."""
    for g in (8, 4, 2):
        if h % g == 0:
            return g
    return 1


# ---------------------------------------------------------------------------
# Generic blocked pallas_call over pytrees
# ---------------------------------------------------------------------------


def _shard_spec(shape, g):
    bs = (shape[0] // g,) + tuple(shape[1:])
    nd = len(shape)
    return pl.BlockSpec(bs, lambda i, _n=nd: (i,) + (0,) * (_n - 1))


def _full_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(tuple(shape), lambda i, _n=nd: (0,) * nd)


def _call_blocked(body, g, shard_in, full_in):
    """Run `body(shard_block, full, block_idx) -> (shard_out, accum_out)`
    over `g` host blocks as ONE pallas_call.

    `shard_in` leaves are blocked on their leading axis (which must be a
    multiple of g: [H], [H, k], or the host-major packed [H*k, C]
    slabs); `full_in` leaves are replicated to every block.  `shard_out`
    leaves are reassembled on the leading axis; `accum_out` leaves (per-
    block partials, any shape) come back stacked [g, ...] for the caller
    to reduce.  0-d leaves are boxed to (1,) across the pallas boundary
    and zero-size leaves are rebuilt as constants inside (an empty array
    carries no data), both transparently.

    Shard outputs whose pytree path matches a shard input of the same
    shape/dtype (state slabs updated in place: hosts, inbox, socks,
    pool, em) alias that input's buffer, so XLA elides the defensive
    copy and the output-init broadcast at every kernel boundary --
    pure buffer reuse, bitwise-neutral."""
    paths_s, td_s = jax.tree_util.tree_flatten_with_path(shard_in)
    flat_s = [l for _p, l in paths_s]
    flat_f, td_f = jax.tree_util.tree_flatten(full_in)

    f_meta = [(l.ndim == 0, l.size == 0, tuple(l.shape), l.dtype)
              for l in flat_f]
    f_pass = [l.reshape(1) if l.ndim == 0 else l
              for l in flat_f if l.size > 0]

    blk_s = [jax.ShapeDtypeStruct((l.shape[0] // g,) + tuple(l.shape[1:]),
                                  l.dtype) for l in flat_s]
    abs_shard = jax.tree_util.tree_unflatten(td_s, blk_s)
    out_sh_av, out_ac_av = jax.eval_shape(
        body, abs_shard, full_in, jax.ShapeDtypeStruct((), jnp.int32))
    sh_paths, td_osh = jax.tree_util.tree_flatten_with_path(out_sh_av)
    sh_av = [a for _p, a in sh_paths]
    ac_av, td_oac = jax.tree_util.tree_flatten(out_ac_av)

    in_path_idx = {jax.tree_util.keystr(p): i
                   for i, (p, _l) in enumerate(paths_s)}
    aliases = {}
    for j, (p, a) in enumerate(sh_paths):
        i = in_path_idx.get(jax.tree_util.keystr(p))
        if i is not None and tuple(flat_s[i].shape[1:]) == tuple(a.shape[1:]) \
                and flat_s[i].dtype == a.dtype:
            aliases[i] = j

    n_s, n_f = len(flat_s), len(f_pass)

    def kernel(*refs):
        rs = refs[:n_s]
        rf = refs[n_s:n_s + n_f]
        ro = refs[n_s + n_f:]
        svals = [r[...] for r in rs]
        it = iter(rf)
        fvals = []
        for boxed, empty_leaf, shape, dtype in f_meta:
            if empty_leaf:
                fvals.append(jnp.zeros(shape, dtype))
            else:
                v = next(it)[...]
                fvals.append(v.reshape(()) if boxed else v)
        s_tree = jax.tree_util.tree_unflatten(td_s, svals)
        f_tree = jax.tree_util.tree_unflatten(td_f, fvals)
        o_sh, o_ac = body(s_tree, f_tree, pl.program_id(0))
        o_flat = jax.tree_util.tree_leaves(o_sh) + \
            [jnp.asarray(x)[None] for x in jax.tree_util.tree_leaves(o_ac)]
        for r, v in zip(ro, o_flat):
            r[...] = v

    out_shape = (
        [jax.ShapeDtypeStruct((a.shape[0] * g,) + tuple(a.shape[1:]),
                              a.dtype) for a in sh_av] +
        [jax.ShapeDtypeStruct((g,) + tuple(a.shape), a.dtype)
         for a in ac_av])
    out_specs = (
        [_shard_spec(s.shape, g) for s in out_shape[:len(sh_av)]] +
        [pl.BlockSpec((1,) + tuple(a.shape),
                      lambda i, _n=a.ndim: (i,) + (0,) * _n)
         for a in ac_av])
    in_specs = ([_shard_spec(l.shape, g) for l in flat_s] +
                [_full_spec(l.shape) for l in f_pass])

    res = pl.pallas_call(
        kernel, grid=(g,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases=aliases,
        interpret=_interpret(),
    )(*flat_s, *f_pass)
    res = list(res) if isinstance(res, (list, tuple)) else [res]
    out_sh = jax.tree_util.tree_unflatten(td_osh, res[:len(sh_av)])
    out_ac = jax.tree_util.tree_unflatten(td_oac, res[len(sh_av):])
    return out_sh, out_ac


def exchange_call(pool, ib, h, params):
    """engine._exchange_core as ONE single-block pallas call: the
    boundary exchange's order/deliver graph (the keyed sort of movers
    by destination, the class-keyed re-sort of ACK-shedding windows,
    segment bounds, the destination-side slot map and row gathers)
    collapses to a single launch per window.  A destination slab
    gathers its movers from any source host, so the exchange cannot
    block on hosts: every grid step sees the full
    arrays, the work runs under `pl.when(step == 0)`, and the grid is
    2 rather than 1 because XLA's while-loop simplifier unrolls
    trip-count-1 loops -- which would dissolve the kernel region back
    into the surrounding graph (no single launch, and nothing for
    kernelcount to classify).  All-integer slab shuffling, so it is
    fusion-context stable (docs/megakernel.md, "f32 stability")."""
    flat_in, td_in = jax.tree_util.tree_flatten({"pool": pool, "inbox": ib})
    in_paths = {jax.tree_util.keystr(p): i for i, (p, _l) in
                enumerate(jax.tree_util.tree_flatten_with_path(
                    {"pool": pool, "inbox": ib})[0])}
    def _core(p, i):
        p2, i2, total, tprot, nfree = engine._exchange_core(
            p, i, h, params)
        return {"pool": p2, "inbox": i2, "total": total,
                "tprot": tprot, "nfree": nfree}

    out_av = jax.eval_shape(_core, pool, ib)
    out_paths, td_out = jax.tree_util.tree_flatten_with_path(out_av)
    flat_av = [a for _p, a in out_paths]
    aliases = {}
    for j, (p, a) in enumerate(out_paths):
        i = in_paths.get(jax.tree_util.keystr(p))
        if i is not None and flat_in[i].shape == a.shape \
                and flat_in[i].dtype == a.dtype:
            aliases[i] = j
    n_in = len(flat_in)

    def kernel(*refs):
        @pl.when(pl.program_id(0) == 0)
        def _work():
            vals = [r[...] for r in refs[:n_in]]
            d = jax.tree_util.tree_unflatten(td_in, vals)
            outs = _core(d["pool"], d["inbox"])
            for r, v in zip(refs[n_in:],
                            jax.tree_util.tree_leaves(outs)):
                r[...] = v

    full = [pl.BlockSpec(tuple(l.shape),
                         lambda i, _n=l.ndim: (0,) * _n)
            for l in flat_in]
    outs = [pl.BlockSpec(tuple(a.shape),
                         lambda i, _n=a.ndim: (0,) * _n)
            for a in flat_av]
    res = pl.pallas_call(
        kernel, grid=(2,), in_specs=full, out_specs=outs,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in flat_av],
        input_output_aliases=aliases, interpret=_interpret(),
    )(*flat_in)
    res = list(res) if isinstance(res, (list, tuple)) else [res]
    out = jax.tree_util.tree_unflatten(td_out, res)
    return (out["pool"], out["inbox"], out["total"], out["tprot"],
            out["nfree"])


# ---------------------------------------------------------------------------
# Persistent window kernel (K_WINDOW)
# ---------------------------------------------------------------------------


def _call_full(core, inputs):
    """Run `core(inputs_pytree) -> outputs_pytree` as ONE full-array,
    single-region pallas call: the exchange_call pattern generalized to
    arbitrary pytrees.  Every grid step sees the full arrays, the work
    runs under `pl.when(step == 0)`, and the grid is 2 rather than 1
    because XLA's while-loop simplifier unrolls trip-count-1 loops --
    which would dissolve the kernel region back into the surrounding
    graph.

    0-d leaves are boxed to (1,) across the pallas boundary and
    zero-size leaves are dropped on the way in / rebuilt as constants on
    the way out (an empty array carries no data), both transparently.
    Output leaves whose pytree path matches an input leaf of the same
    shape/dtype alias that input's buffer (state slabs updated in
    place), eliding the defensive copy per crossing leaf."""
    paths_in, td_in = jax.tree_util.tree_flatten_with_path(inputs)
    flat_in = [l for _p, l in paths_in]
    in_meta = [(l.ndim == 0, l.size == 0, tuple(l.shape), l.dtype)
               for l in flat_in]
    pass_in = []
    pass_idx = {}              # original leaf index -> passed operand idx
    for i, l in enumerate(flat_in):
        if l.size == 0:
            continue
        pass_idx[i] = len(pass_in)
        pass_in.append(l.reshape(1) if l.ndim == 0 else l)

    out_av = jax.eval_shape(core, inputs)
    out_paths, td_out = jax.tree_util.tree_flatten_with_path(out_av)
    out_meta = [(a.ndim == 0, a.size == 0, tuple(a.shape), a.dtype)
                for _p, a in out_paths]

    in_by_path = {jax.tree_util.keystr(p): i
                  for i, (p, _l) in enumerate(paths_in)}
    out_shapes = []
    aliases = {}
    for (p, _a), (boxed, empty_leaf, shape, dtype) in zip(out_paths,
                                                          out_meta):
        if empty_leaf:
            continue
        j = len(out_shapes)
        out_shapes.append(jax.ShapeDtypeStruct((1,) if boxed else shape,
                                               dtype))
        i = in_by_path.get(jax.tree_util.keystr(p))
        if i is not None and i in pass_idx \
                and in_meta[i][2] == shape and in_meta[i][3] == dtype:
            aliases[pass_idx[i]] = j

    n_in = len(pass_in)

    def kernel(*refs):
        @pl.when(pl.program_id(0) == 0)
        def _work():
            it = iter(refs[:n_in])
            vals = []
            for boxed, empty_leaf, shape, dtype in in_meta:
                if empty_leaf:
                    vals.append(jnp.zeros(shape, dtype))
                else:
                    v = next(it)[...]
                    vals.append(v.reshape(()) if boxed else v)
            tree = jax.tree_util.tree_unflatten(td_in, vals)
            outs = core(tree)
            ro = iter(refs[n_in:])
            for v, (boxed, empty_leaf, _s, _d) in zip(
                    jax.tree_util.tree_leaves(outs), out_meta):
                if empty_leaf:
                    continue
                r = next(ro)
                r[...] = jnp.asarray(v)[None] if boxed else v

    in_specs = [pl.BlockSpec(tuple(l.shape),
                             lambda i, _n=l.ndim: (0,) * _n)
                for l in pass_in]
    out_specs = [pl.BlockSpec(tuple(s.shape),
                              lambda i, _n=len(s.shape): (0,) * _n)
                 for s in out_shapes]
    res = pl.pallas_call(
        kernel, grid=(2,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, input_output_aliases=aliases,
        interpret=_interpret(),
    )(*pass_in)
    res = list(res) if isinstance(res, (list, tuple)) else [res]
    it = iter(res)
    leaves = []
    for boxed, empty_leaf, shape, dtype in out_meta:
        if empty_leaf:
            leaves.append(jnp.zeros(shape, dtype))
        else:
            v = next(it)
            leaves.append(v.reshape(()) if boxed else v)
    return jax.tree_util.tree_unflatten(td_out, leaves)


def window_fused(state: SimState, params, app, t_target):
    """One whole conservative window as ONE pallas region (K_WINDOW):
    the boundary exchange, the per-window scan, the window bounds, the
    netem advance, and the micro-step while loop with its gmin
    loop-continue predicate all run inside a single kernel invocation,
    so a window costs O(1) launches instead of O(steps x phases).

    The body is `engine._window_body_ref` -- reference implementations
    only (a pallas region cannot nest another pallas_call), with the
    whole params pytree and t_target riding in as kernel operands
    (closure-captured tracers are illegal in a kernel body).  The f32
    contract that makes this bitwise-admissible is the in-kernel one
    documented in docs/megakernel.md ("Persistent window kernel"):
    every op inside is integer, exactly-rounded f32, or an f64
    transcendental that lowers to a context-independent libm call
    (phold's delay draw moved to f64 log1p in the ensemble round for
    exactly this property).

    Returns (state, t_h, gmin, ws, we); the caller runs the
    window-close instrumentation hooks on ws/we outside the region."""
    t_target = jnp.asarray(t_target, I64)

    def _core(d):
        st, t_h, gmin, ws, we = engine._window_body_ref(
            d["st"], d["par"], app, d["tt"])
        return {"st": st, "t_h": t_h, "gmin": gmin, "ws": ws, "we": we}

    out = _call_full(_core, {"st": state, "par": params, "tt": t_target})
    return out["st"], out["t_h"], out["gmin"], out["ws"], out["we"]


# ---------------------------------------------------------------------------
# Fused micro-step
# ---------------------------------------------------------------------------


def _hoff_blk(base, i, hb):
    """Global host id of a block's row 0: the shard offset (if any) plus
    the block offset.  Installing it as the block state's hoff makes
    host_ids()/_lrows()/_loopback_insert address globally/locally
    exactly as the mesh path already does."""
    off = jnp.asarray(i, I32) * jnp.asarray(hb, I32)
    if base is not None:
        off = off + base.astype(I32)
    return off


def _rebuild_params(params, local, rep):
    """Blocked NetParams: every pytree leaf replaced from kernel inputs
    (closure-captured leaves would be baked into the kernel as
    constants), statics carried over from the traced params object."""
    return params.replace(**local, **rep)


def _or_all(x):
    return jax.lax.reduce(x, jnp.zeros((), x.dtype),
                          jax.lax.bitwise_or, (0,))


def microstep_fused(state: SimState, params, app, t_h, window_end,
                    ctx=None):
    """One micro-step through the fused kernels.  Returns
    (state, t_h_next, gmin_next): the post-step per-host scan rides out
    of K_TRANSPORT, so callers need no separate _scan_all.

    Bitwise-identical to `engine._microstep_core` followed by
    `engine._scan_all` -- the kernel bodies call those same reference
    implementations on blocked rows (see module docstring)."""
    from ..transport import tcp as tcp_mod

    if ctx is None:
        ctx = engine._window_ctx(state, params)
    bw_up, bw_dn, alive = ctx

    h = state.hosts.num_hosts
    g = _grid(h)
    hb = h // g
    uses_tcp = engine._uses_tcp(app)
    if uses_tcp and state.inbox.blk.shape[1] < ICOLS:
        raise ValueError(
            "this world's inbox was built narrow (uses_tcp=False in "
            "make_sim_state) but the app uses TCP; TCP segments need the "
            "TS/SACK inbox columns")

    window_end = jnp.asarray(window_end, I64)
    active = t_h < window_end
    tick_t = jnp.where(active, t_h, window_end)
    state = state.replace(
        hosts=state.hosts.replace(t_resume=jnp.where(
            active, jnp.asarray(INV, I64), state.hosts.t_resume)))

    d_rounds = max(1, int(getattr(app, "rx_batch", 1)))
    # rx_batch bound, evaluated at batch start exactly where the
    # reference evaluates it (post re-arm, pre any rx mutation); the
    # kernel does not carry app state, so it rides in per-host.
    aux0 = engine._aux_times(state, params, app) if d_rounds > 1 else None

    if uses_tcp:
        n_lanes = emit.NUM_SLOTS + max(0, d_rounds - 1)
    else:
        n_lanes = emit.SLOT_APP + max(1, int(getattr(app, "app_tx_lanes",
                                                     1)))
    cols = state.pool.blk.shape[1]
    nm = state.nm
    base = state.hoff

    p_local = {k: getattr(params, k) for k in _PARAMS_LOCAL}
    p_rep = {k: getattr(params, k) for k in _PARAMS_REP}
    if params.hosts_real is not None:
        p_rep["hosts_real"] = params.hosts_real

    # ---- K_DELIVER: the whole _rx_phase on a block of hosts -----------
    shard_in = dict(hosts=state.hosts, inbox=state.inbox,
                    socks=state.socks, tick_t=tick_t, active=active,
                    bw_dn=bw_dn, p_local=p_local)
    if alive is not None:
        shard_in["alive"] = alive
    if aux0 is not None:
        shard_in["aux0"] = aux0
    full_in = dict(p_rep=p_rep, we=window_end)
    if nm is not None:
        full_in["nm"] = nm
    if base is not None:
        full_in["hoff"] = base

    def k_deliver(s, f, i):
        par = _rebuild_params(params, s["p_local"], f["p_rep"])
        nm_blk = None
        if nm is not None:
            nm_blk = f["nm"].replace(
                killed=jnp.zeros_like(f["nm"].killed))
        st = SimState(
            now=None, pool=None, inbox=s["inbox"], socks=s["socks"],
            hosts=s["hosts"], err=jnp.zeros((), I32), nm=nm_blk,
            hoff=_hoff_blk(f.get("hoff"), i, hb))
        em = emit.empty(hb, n_lanes, cols=cols)
        st, em, delivered_n, t_post = engine._rx_phase(
            st, par, em, s["tick_t"], s["active"], app, f["we"],
            bw_dn=s["bw_dn"], alive=s.get("alive"),
            aux_bound=s.get("aux0"))
        out = dict(hosts=st.hosts, inbox=st.inbox, socks=st.socks,
                   em=em, delivered_n=delivered_n, t_post=t_post)
        acc = dict(err=st.err)
        if nm is not None:
            acc["killed"] = st.nm.killed
        return out, acc

    o, a = _call_blocked(k_deliver, g, shard_in, full_in)
    state = state.replace(hosts=o["hosts"], inbox=o["inbox"],
                          socks=o["socks"],
                          err=state.err | _or_all(a["err"]))
    if nm is not None:
        state = state.replace(nm=state.nm.replace(
            killed=state.nm.killed + jnp.sum(a["killed"])))
    em, delivered_n, t_post = o["em"], o["delivered_n"], o["t_post"]

    # ---- between kernels: timers + app tick (main-graph f32 context) --
    if uses_tcp:
        state, em = tcp_mod.run_timers(state, params, em, t_post, active)
    t_app = None
    if app is not None:
        if getattr(app, "wants_window_end", False):
            state, em = app.on_tick(state, params, em, t_post, active,
                                    window_end=window_end)
        else:
            state, em = app.on_tick(state, params, em, t_post, active)
        # Post-step app wake times: transport never touches app state,
        # so the scan term is exact when computed here and carried in.
        t_app = jnp.broadcast_to(
            jnp.asarray(app.next_time(state), I64), (h,))

    # ---- K_TRANSPORT: transmit -> stage -> drain -> accounting -> scan
    shard_in2 = dict(hosts=state.hosts, pool=state.pool,
                     inbox=state.inbox, socks=state.socks, em=em,
                     tick_t=tick_t, active=active, t_post=t_post,
                     bw_up=bw_up, delivered_n=delivered_n,
                     p_local=p_local)
    if t_app is not None:
        shard_in2["t_app"] = t_app
    full_in2 = dict(p_rep=p_rep)
    if nm is not None:
        full_in2["nm"] = nm
    if base is not None:
        full_in2["hoff"] = base

    def k_transport(s, f, i):
        par = _rebuild_params(params, s["p_local"], f["p_rep"])
        nm_blk = None
        if nm is not None:
            nm_blk = f["nm"].replace(
                killed=jnp.zeros_like(f["nm"].killed))
        st = SimState(
            now=None, pool=s["pool"], inbox=s["inbox"],
            socks=s["socks"], hosts=s["hosts"],
            err=jnp.zeros((), I32), nm=nm_blk,
            hoff=_hoff_blk(f.get("hoff"), i, hb))
        em_b, t_post_b, active_b = s["em"], s["t_post"], s["active"]
        if uses_tcp:
            st, em_b = tcp_mod.transmit(st, par, em_b, t_post_b,
                                        active_b)
        st, _placed = engine._stage_emissions(st, par, em_b, t_post_b,
                                              active_b, app,
                                              bw_up=s["bw_up"])
        # Parked-TX drain.  skip_refill: staging just refilled this
        # bucket at the same instant, so the reference's second refill
        # accrues exactly 0 tokens.  Without it the diet gate's
        # refill-only branch is the identity, so the gate collapses to
        # cond(any-parked, drain-body, identity).
        if params.kernel_diet:
            st = jax.lax.cond(
                jnp.any(st.pool.stage == STAGE_TX_QUEUED),
                lambda x: engine._tx_drain_body(
                    x, par, t_post_b, active_b, s["bw_up"],
                    skip_refill=True),
                lambda x: x, st)
        else:
            st = engine._tx_drain_body(st, par, t_post_b, active_b,
                                       s["bw_up"], skip_refill=True)

        # Virtual-CPU accounting (engine._microstep_core tail).
        cpu_on = par.cpu_ns_per_event > 0
        events = s["delivered_n"].astype(I64) + \
            jnp.sum(em_b.valid, axis=1).astype(I64)
        cost = par.cpu_ns_per_event * events
        avail = jnp.maximum(st.hosts.cpu_avail, s["tick_t"])
        new_avail = jnp.where(cpu_on & active_b, avail + cost,
                              st.hosts.cpu_avail)
        st = st.replace(hosts=st.hosts.replace(cpu_avail=new_avail))

        # Post-step per-host scan (engine._scan_all on the block; the
        # app term was computed outside and rides in).
        ib = st.inbox
        ki = ib.capacity // hb
        t2 = ib.times().reshape(hb, ki)
        drive = (ib.stage == STAGE_IN_FLIGHT).reshape(hb, ki)
        t_in = jnp.min(jnp.where(drive, t2, jnp.asarray(INV, I64)),
                       axis=1)
        aux = st.hosts.t_resume
        if uses_tcp:
            t_tmr = jnp.minimum(
                jnp.minimum(jnp.min(st.socks.t_rto, axis=1),
                            jnp.min(st.socks.t_persist, axis=1)),
                jnp.minimum(jnp.min(st.socks.t_delack, axis=1),
                            jnp.min(st.socks.t_tw, axis=1)),
            )
            aux = jnp.minimum(aux, t_tmr)
        if "t_app" in s:
            aux = jnp.minimum(aux, s["t_app"])
        th = engine._cpu_clamp(st, par, jnp.minimum(t_in, aux))

        out = dict(hosts=st.hosts, pool=st.pool, inbox=st.inbox,
                   socks=st.socks, th=th)
        acc = dict(err=st.err, ev=jnp.sum(events))
        if nm is not None:
            acc["killed"] = st.nm.killed
        return out, acc

    o2, a2 = _call_blocked(k_transport, g, shard_in2, full_in2)
    state = state.replace(
        hosts=o2["hosts"], pool=o2["pool"], inbox=o2["inbox"],
        socks=o2["socks"], err=state.err | _or_all(a2["err"]),
        n_steps=state.n_steps + 1,
        n_events=state.n_events + jnp.sum(a2["ev"]))
    if nm is not None:
        state = state.replace(nm=state.nm.replace(
            killed=state.nm.killed + jnp.sum(a2["killed"])))
    th = o2["th"]
    return state, th, jnp.min(th)
