"""chip_smoke.py off the chip, and the diff helper its phase (e) uses."""

import json

from shadow1_tpu import diff, sim
from shadow1_tpu.core import simtime

MS = simtime.SIMTIME_ONE_MILLISECOND


def test_chip_smoke_refuses_without_tpu(capsys):
    import chip_smoke
    assert chip_smoke.main([]) == 1
    assert chip_smoke.main(["--four-chips"]) == 1
    out = capsys.readouterr()
    assert "no TPU here" in out.err
    assert not any(json.loads(ln).get("ok")
                   for ln in out.out.splitlines() if ln.startswith("{"))


def test_compare_states_names_the_differing_field():
    state, params, app = sim.build_phold(num_hosts=8, stop_time=50 * MS)
    a = sim.run(state, params, app)
    b = a.replace(app=a.app.replace(next_send=a.app.next_send.at[3].add(1)))
    assert diff.compare_states(a, a) == {"groups_differing": [],
                                         "fields": []}
    rep = diff.compare_states(a, b, "pool", max_elements=1)
    assert rep["groups_differing"] == ["app"]
    (field,) = rep["fields"]
    assert field["field"] == "app.next_send"
    assert field["elements_differing"] == 1
    assert field["first"][0]["host"] == 3
