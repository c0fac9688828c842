"""A seed changes a world's data and never its shapes, so one compiled
executable serves every seed the benchmark is given."""

import pytest

import run
import world

BENCH = run.load_json("BENCHMARK.json")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_two_seeds_share_one_shape_key(cell):
    from shadow1_tpu.shapes.key import shape_key
    r = run.resolve(BENCH, cell)
    keys, inputs = [], []
    for seed in (1, 2**31 + 7):
        state, params, _app, inp = world.build(r["config"], r["traffic"],
                                               seed)
        keys.append(shape_key(state, params))
        inputs.append(inp)
    assert keys[0] == keys[1]
    for name in inputs[0]:
        # the same set of values, in another order
        assert sorted(inputs[0][name]) == sorted(inputs[1][name])
        assert list(inputs[0][name]) != list(inputs[1][name])
