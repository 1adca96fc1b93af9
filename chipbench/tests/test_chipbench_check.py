"""The check that decides ``correct`` has to fail what it is there to
catch, on the CPU, through the harness's own run of a small cell:

- the control, the plain reference put in the program's place with its
  products one precision below the configuration's, comes out not correct.
  KWT-1 runs here at its published widths, so its control is held to the
  configuration's own limits. The language model runs here far smaller
  than on the chip, where its fp8 control reads about twice the limit of
  the first step's loss gap; here the control has to read at least three
  times what the program reads in one number;
- a run with each fault of ``faults.py`` planted underneath the check's
  capture comes out not correct: a local update that returns the global
  model, half of each batch left out, an update altered where it is
  produced, an aggregate that leaves the global model as it was, that
  averages half the contributors or that writes a NaN into it, and an
  evaluation scored against the wrong labels. No number reads NaN: the
  aggregate with a NaN reads ``inf`` ulps.
"""
import math

import pytest

from chipbench.faults import FAULTS
from chipbench.tests.small import run_small

CELLS = ["kwt1.paper100", "smollm-360m.paper100"]


@pytest.mark.parametrize("name,at_published_widths",
                         [("kwt1.paper100", True),
                          ("smollm-360m.paper100", False)])
def test_the_control_is_not_correct(name, at_published_widths):
    for seed in (3,):
        sound = run_small(name, seed=seed)
        assert sound["correct"] is True, sound["check"]
        control = run_small(name, seed=seed, control=True)
        if at_published_widths:
            assert control["correct"] is False, control["check"]
        else:
            assert any(control["check"][k]["value"]
                       >= 3 * sound["check"][k]["value"]
                       for k in sound["check"] if k != "foreign_rows"), (
                sound["check"], control["check"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_planted_fault_is_not_correct(name, fault):
    out = run_small(name, seed=11, fault=FAULTS[fault])
    assert out["correct"] is False, out["check"]
    assert not any(math.isnan(c["value"]) for c in out["check"].values())
    if fault == "nonfinite_aggregate":
        assert out["check"]["aggregate_ulps"]["value"] == math.inf
