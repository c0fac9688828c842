"""The one generator: a cell's world from its configuration, its traffic
mix and the seed.

A configuration file names the program's builder and the world's shapes
(`builder`, `kwargs`); a traffic file adds the load (`kwargs`) and the
inputs the benchmark draws itself (`inputs`).  The seed reaches the
builder (every draw the program makes is keyed by it) and orders the
benchmark's own inputs; it never changes a shape, so one compiled
executable serves every seed.

An entry of `inputs` writes a leaf of the built state at rows
`start, start + step, ...`: the values `base_ns + step_ns * (i % modulo)`
for the i-th such row, shuffled by the seed when `shuffle` is true.  So
every seed gets the same set of values, in another order.
"""

from __future__ import annotations

import importlib

import numpy as np


def builder_kwargs(config, traffic, seed=None, overrides=None):
    kw = {**config["kwargs"], **traffic.get("kwargs", {}),
          **(overrides or {})}
    if seed is not None:
        kw["seed"] = int(seed)
    return kw


def draw_inputs(traffic, num_rows, seed):
    """{name: (leaf path, row indices, int64 values)} for the mix's inputs."""
    out = {}
    for k, spec in enumerate(traffic.get("inputs", [])):
        rows = np.arange(spec["start"], num_rows, spec["step"])
        i = np.arange(len(rows))
        vals = (spec["base_ns"] + spec["step_ns"] * (i % spec["modulo"])
                ).astype(np.int64)
        if spec.get("shuffle"):
            vals = vals[np.random.default_rng([int(seed), k]).permutation(
                len(vals))]
        out[spec["name"]] = (spec["leaf"], rows, vals)
    return out


def _get(obj, path):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def _set(obj, path, value):
    head, _, rest = path.partition(".")
    if not rest:
        return obj.replace(**{head: value})
    return obj.replace(**{head: _set(getattr(obj, head), rest, value)})


def num_rows(config, traffic, overrides=None):
    """Host rows of the world, from its builder arguments."""
    kw = builder_kwargs(config, traffic, overrides=overrides)
    rows = config["rows"]
    return int(kw[rows["kwarg"]]) * int(rows.get("per", 1))


def build(config, traffic, seed, overrides=None, program_kw=None):
    """(state, params, app, inputs): the program's world for this seed,
    with the benchmark's drawn inputs written into it.  `program_kw`, a
    function of the builder arguments, changes them for the program
    alone (a planted fault)."""
    import jax
    mod_name, _, fn_name = config["builder"].rpartition(".")
    builder = getattr(importlib.import_module(mod_name), fn_name)
    kw = builder_kwargs(config, traffic, seed, overrides)
    state, params, app = builder(**(program_kw(kw) if program_kw else kw))
    inputs = draw_inputs(traffic, num_rows(config, traffic, overrides), seed)
    for leaf_path, rows, vals in inputs.values():
        host = np.array(jax.device_get(_get(state, leaf_path)))
        host[rows] = vals
        state = _set(state, leaf_path, host)
    # Every leaf committed to the first device, as the launches' outputs
    # are: an initial state that differed in that would key a second
    # compile of the same program.
    state, params = jax.device_put((state, params), jax.devices()[0])
    return state, params, app, {k: v[2] for k, v in inputs.items()}
