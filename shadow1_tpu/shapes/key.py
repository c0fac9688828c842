"""Shape identity and bucketing for compiled worlds.

XLA compiles one executable per distinct input SHAPE (plus the static
flags baked into the graph), and a run_until compile of a TCP world
costs ~30-60s -- so a sweep of dozens of world configs pays the
compile tax dozens of times (ROADMAP: "kill the 35s-per-world compile
tax").  This module makes that tax amortizable:

* `ShapeKey` canonicalizes every determinant of the compiled run_until
  graph's shape: host count H, the per-host pool/inbox slabs, the packed
  block widths (18 UDP-only / 28 TCP), socket slots, the routing vertex
  count V (route_blk is [V*V, 5]), the static NetParams flags
  (cong/has_iface_buf/pds_trail/has_loss/has_jitter/kernel_diet/
  megakernel/persistent, with route_narrow implied by has_jitter), and
  which
  present-or-None blocks
  ride the state (nm/cap/log/log_level/tr/fr/hoff) with their leaf
  shapes.

* `bucket_for(key)` rounds H (and V) up a small geometric ladder so
  different-sized scenarios land on the SAME shape; pad_world_to_bucket
  (bucket.py) then pads the world to the bucket while keeping real-host
  rows bitwise identical to the exact-size trajectory.

Two worlds sharing a bucketed ShapeKey share one compiled graph
PROVIDED their jit statics also match: the app object (__eq__/__hash__
over its config) and the NetParams statics are part of the jit cache
key.  Builders that want sharing should size pools per-slab
(pool_capacity = num_hosts * slab), since a fixed total capacity makes
the slab -- a shape determinant -- vary with H.  See docs/shapes.md.
"""

from __future__ import annotations

import dataclasses

import jax

# Geometric host ladder (x4 per rung): small enough that padding waste
# is bounded (<4x rows, and padded rows are inert so they cost little
# work), large enough that a whole scenario sweep lands on a handful of
# buckets.  Every rung is divisible by any power-of-two device count up
# to 64, so bucketed worlds compose with pad_world_to_mesh without a
# second padding pass (docs/parallel.md).
HOST_LADDER = (64, 256, 1024, 4096, 16384, 65536, 262144, 1048576)

# Vertex ladder for route_blk's [V*V] row axis: quadratic cost, so it
# gets smaller rungs.  Builders cap V at 256 (sim.build_phold) but
# config topologies can exceed it.
VERTEX_LADDER = (16, 64, 256, 1024, 4096)

# The present-or-None SimState blocks whose presence (and shape) changes
# the traced graph.  `app` is keyed separately by type + leaf shapes.
# `scope` (the flowscope sampling block) includes its static
# sample_flows/sample_links flags via leaf shapes + jit statics.
_STATE_BLOCKS = ("nm", "cap", "log", "log_level", "tr", "fr", "scope",
                 "sentinel", "lineage", "dg", "hoff")


@dataclasses.dataclass(frozen=True)
class ShapeKey:
    """Canonical shape identity of a (state, params, app) world.  Two
    worlds with equal ShapeKeys (and equal jit statics: app config,
    NetParams flags already folded in here) trace identical graphs."""

    hosts: int
    vertices: int
    pool_slab: int
    inbox_slab: int
    sock_slots: int
    cols: int           # packed pool/outbox width: 18 UDP-only, 28 TCP
    icols: int          # inbox width: 14 UDP-only, 24 TCP
    has_loss: bool
    has_jitter: bool
    kernel_diet: bool
    megakernel: bool
    persistent: bool
    cong: str
    has_iface_buf: bool
    pds_trail: bool
    app: str | None             # app state type name, or None
    blocks: tuple               # ((name, leaf-shape signature), ...)

    @property
    def route_narrow(self) -> bool:
        """Jitter-free worlds gather the narrow 3-column routing rows
        (core/params.py route_narrow); implied by has_jitter."""
        return not self.has_jitter


def _leaf_shapes(obj):
    """Shape signature of a pytree block: the tuple of its leaf shapes.
    Good enough to distinguish any two blocks that trace differently."""
    return tuple(tuple(getattr(leaf, "shape", ()))
                 for leaf in jax.tree_util.tree_leaves(obj))


def shape_key(state, params) -> ShapeKey:
    """Read the ShapeKey off a built world."""
    h = int(state.hosts.num_hosts)
    blocks = tuple(
        (name, _leaf_shapes(getattr(state, name)))
        for name in _STATE_BLOCKS if getattr(state, name) is not None)
    return ShapeKey(
        hosts=h,
        vertices=int(params.n_vertices),
        pool_slab=int(state.pool.capacity) // h,
        inbox_slab=int(state.inbox.capacity) // h,
        sock_slots=int(state.socks.slots),
        cols=int(state.pool.blk.shape[1]),
        icols=int(state.inbox.blk.shape[1]),
        has_loss=bool(params.has_loss),
        has_jitter=bool(params.has_jitter),
        kernel_diet=bool(params.kernel_diet),
        megakernel=bool(params.megakernel),
        persistent=bool(params.persistent),
        cong=str(params.cong),
        has_iface_buf=bool(params.has_iface_buf),
        pds_trail=bool(params.pds_trail),
        app=(type(state.app).__name__ if state.app is not None else None),
        blocks=blocks,
    )


def key_manifest(key: ShapeKey) -> dict:
    """JSON-serializable form of a ShapeKey for checkpoint manifests
    (checkpoint.py): every static as a plain scalar, the present-or-None
    block signatures as {name: [[shape...], ...]}.  Round-trips through
    json.dumps/loads bitwise, so saved and freshly-computed manifests
    compare with plain ==."""
    d = dataclasses.asdict(key)
    d["blocks"] = {name: [list(s) for s in sig]
                   for name, sig in key.blocks}
    return d


def describe_key_mismatch(saved: dict, current: dict,
                          a_label: str = "checkpoint",
                          b_label: str = "template") -> str | None:
    """Name the first difference between two key_manifest() dicts, or
    None when they match.  Block differences name the BLOCK (a missing
    flight recorder, a log ring sized differently); static differences
    name the STATIC (cong, megakernel, pool_slab, ...) -- the load-time
    diagnosis checkpoint.load prints instead of a bare structure error.
    `a_label`/`b_label` rename the two sides for non-checkpoint callers
    (ensemble.stack compares world 0 against world k)."""
    sb = saved.get("blocks", {})
    cb = current.get("blocks", {})
    for name in _STATE_BLOCKS:
        in_s, in_c = name in sb, name in cb
        if in_s and not in_c:
            return (f"block {name!r} is present in the {a_label} but "
                    f"absent on the {b_label} (install it before loading)"
                    if a_label == "checkpoint" else
                    f"block {name!r} is present on the {a_label} but "
                    f"absent on the {b_label}")
        if in_c and not in_s:
            return (f"block {name!r} is present on the {b_label} but "
                    f"absent in the {a_label} (build the template "
                    f"without it; add instrumentation AFTER loading)"
                    if a_label == "checkpoint" else
                    f"block {name!r} is present on the {b_label} but "
                    f"absent on the {a_label}")
        if in_s and sb[name] != cb[name]:
            return (f"block {name!r} leaf shapes differ: {a_label} "
                    f"{sb[name]} vs {b_label} {cb[name]}")
    for field in sorted(set(saved) | set(current)):
        if field == "blocks":
            continue
        if saved.get(field) != current.get(field):
            return (f"static {field!r} differs: {a_label} "
                    f"{saved.get(field)!r} vs {b_label} "
                    f"{current.get(field)!r}")
    return None


def _round_up(n: int, ladder) -> int:
    for rung in ladder:
        if rung >= n:
            return rung
    return n


def bucket_for(key: ShapeKey, ladder=HOST_LADDER) -> ShapeKey:
    """The bucket a world belongs to: hosts rounded up the geometric
    ladder, vertices rounded up VERTEX_LADDER; every other determinant
    (slab, widths, flags, blocks) is preserved exactly -- rounding a
    slab is trajectory-visible (overflow drops, slot indices), so slabs
    never bucket.

    Beyond the ladder the host count stays exact."""
    hb = _round_up(key.hosts, ladder)
    vb = _round_up(key.vertices, VERTEX_LADDER)
    if hb == key.hosts and vb == key.vertices:
        return key
    return dataclasses.replace(key, hosts=hb, vertices=vb)
