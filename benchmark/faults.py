"""Broken versions of the timed path: the control and the faults that the
correctness check has to catch (benchmark/tests/test_faults.py at small
sizes on the CPU; benchmark/readings.py at the cells' sizes on the chip).

Each is planted from outside the program: an application object whose
tick is wrapped or whose settings differ (passed to `sim.run` like the
real one, under a jit key of its own), a state or a builder argument
changed for the program but not for the check, or a JAX function swapped
while the program is traced.

* control: the configuration's guarantee broken once -- phold loses one
  in 64 hosts' delivered messages without a count; onion's clients
  write one 512-byte cell fewer than the circuit's bytes;
* unchanged: a launch that returns its state unchanged;
* half: half of the hosts (the odd ones) left out of every tick, their
  own events withdrawn;
* altered: an answer altered where it is produced -- phold counts one in
  64 hosts' receptions twice; onion's servers count one byte more than
  they took in;
* slow: less work a simulated second -- phold draws its delays at 1.5
  times the configured mean; onion's TCP runs with a sixteenth of the
  configured socket buffers, so its windows and its circuits are slower;
* local: phold sends every message to a host within 1/64 of the hosts
  after its sender, not to one drawn from all the others;
* self: phold sends every message back to its sender;
* short_latency: phold's links carry messages in a quarter of the
  configured latency.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

KINDS = ("control", "unchanged", "half", "altered", "slow", "local",
         "self", "short_latency")
# The kinds that apply to each plain reference.
APPLIES = {"phold": KINDS,
           "onion": ("control", "unchanged", "half", "altered", "slow")}


def _ids(state):
    from shadow1_tpu.core.state import host_ids
    return host_ids(state, jnp.int32)


def _wrap(app, name, tick, next_time=None):
    """A copy of `app` whose on_tick is `tick(base_on_tick, ...)` (and
    next_time `next_time(base_next_time, state)`), keyed apart from the
    real app in every jit cache."""
    base = type(app)

    class Planted(base):
        def on_tick(self, state, params, em, tick_t, active, **kw):
            return tick(super().on_tick, state, params, em, tick_t,
                        active, **kw)

        def next_time(self, state):
            if next_time is None:
                return super().next_time(state)
            return next_time(super().next_time, state)

        def __hash__(self):
            return hash((name, base.__hash__(self)))

        def __eq__(self, other):
            return type(other) is type(self) and base.__eq__(self, other)

    Planted.__name__ = f"{base.__name__}_{name}"
    planted = object.__new__(Planted)
    planted.__dict__.update(app.__dict__)
    return planted


def _half(on_tick, state, params, em, tick_t, active, **kw):
    return on_tick(state, params, em, tick_t,
                   active & (_ids(state) % 2 == 0), **kw)


def _half_next(next_time, state):
    # The odd hosts' own events are withdrawn too: a due event that no
    # tick serves would hold the window's micro-step loop forever.
    from shadow1_tpu.core.simtime import SIMTIME_INVALID
    return jnp.where(_ids(state) % 2 == 0, next_time(state),
                     jnp.asarray(SIMTIME_INVALID, jnp.int64))


def _phold_lose(on_tick, state, params, em, tick_t, active, **kw):
    recv0 = state.app.recv
    state, em = on_tick(state, params, em, tick_t, active, **kw)
    a = state.app
    got = (a.recv - recv0 > 0) & (_ids(state) % 64 == 0) & (a.pending > 0)
    return state.replace(app=a.replace(
        pending=a.pending - got.astype(a.pending.dtype))), em


def _phold_double(on_tick, state, params, em, tick_t, active, **kw):
    recv0 = state.app.recv
    state, em = on_tick(state, params, em, tick_t, active, **kw)
    a = state.app
    extra = jnp.where(_ids(state) % 64 == 0, a.recv - recv0, 0)
    return state.replace(app=a.replace(recv=a.recv + extra)), em


def _onion_overcount(on_tick, state, params, em, tick_t, active, **kw):
    fwd0 = state.app.forwarded
    state, em = on_tick(state, params, em, tick_t, active, **kw)
    a = state.app
    took = (a.role == 2) & (a.forwarded > fwd0)
    return state.replace(app=a.replace(
        forwarded=a.forwarded + took.astype(a.forwarded.dtype))), em


def _onion_short_client(state, params, app):
    a = state.app
    total = jnp.where(a.role == 0, a.total - 512, a.total)
    return state.replace(app=a.replace(total=total)), params, app


def _phold_slow(state, params, app):
    slow = object.__new__(type(app))
    slow.__dict__.update(app.__dict__)
    slow.mean_delay_ns = app.mean_delay_ns * 3 // 2
    return state, params, slow


def _phold_dst(name, pick):
    """A plant whose phold app draws its destinations by
    `pick(drawn, host_ids, num_hosts)` from the ones it would draw."""
    def plant_fn(state, params, app):
        base = type(app)

        class Planted(base):
            def _pick_dst(self, params, host_ids, ctr, num_hosts):
                drawn = super()._pick_dst(params, host_ids, ctr, num_hosts)
                return pick(drawn, host_ids.astype(drawn.dtype), num_hosts)

            def __hash__(self):
                return hash((name, base.__hash__(self)))

            def __eq__(self, other):
                return type(other) is type(self) and base.__eq__(self, other)

        planted = object.__new__(Planted)
        planted.__dict__.update(app.__dict__)
        return state, params, planted
    return plant_fn


def _local(drawn, ids, n):
    band = max(n // 64, 2)
    return (ids + 1 + ((drawn - ids) % n) % (band - 1)) % n


def _self(drawn, ids, n):
    del drawn, n
    return ids


def _onion_small_buffers(state, params, app):
    s = state.socks
    return state.replace(socks=s.replace(def_snd_buf=s.def_snd_buf // 16,
                                         def_rcv_buf=s.def_rcv_buf // 16)
                         ), params, app


def _quarter_latency(kw):
    return {**kw, "latency_ns": kw["latency_ns"] // 4}


def plant(kind, reference):
    """(plant function for run_cell or None, a function of the builder
    arguments that gives the program's own or None, context manager) for
    one broken path of a world whose plain reference is `reference`."""
    none = contextlib.nullcontext()
    if kind not in APPLIES[reference]:
        raise KeyError(f"{kind} does not apply to {reference}")
    if kind == "control":
        if reference == "onion":
            return _onion_short_client, None, none
        return (lambda s, p, a: (s, p, _wrap(a, "lose", _phold_lose)),
                None, none)
    if kind == "half":
        return (lambda s, p, a: (s, p, _wrap(a, "half", _half, _half_next)),
                None, none)
    if kind == "altered":
        tick = _onion_overcount if reference == "onion" else _phold_double
        return (lambda s, p, a: (s, p, _wrap(a, "altered", tick)),
                None, none)
    if kind == "unchanged":
        return None, None, _swap("shadow1_tpu.sim", "run",
                                 lambda state, *a, **k: state)
    if kind == "slow":
        return (_onion_small_buffers if reference == "onion"
                else _phold_slow), None, none
    if kind == "local":
        return _phold_dst("local", _local), None, none
    if kind == "self":
        return _phold_dst("self", _self), None, none
    if kind == "short_latency":
        return None, _quarter_latency, none
    raise KeyError(kind)


@contextlib.contextmanager
def _swap(module, attr, value):
    """`module.attr` is `value` inside; programs traced before or inside
    are dropped from the in-memory caches on both sides."""
    import importlib
    mod = importlib.import_module(module)
    old = getattr(mod, attr)
    jax.clear_caches()
    setattr(mod, attr, value)
    try:
        yield
    finally:
        setattr(mod, attr, old)
        jax.clear_caches()
