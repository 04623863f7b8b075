"""The control of each check fails it: the plain reference computed one
precision step below the configuration's, put in the program's place,
reads above at least one of the cell's limits (``bench/limits``), while
the program itself reads within them.  Small sizes; on the CPU the lower
precision is written out with explicit bfloat16 roundings."""
import pytest

from bench import control
from bench.drivers import common


def _sweep(cell):
    cell.config["n_samples"] = 8000
    cell.traffic["rounds"] = 10


def _serve(cell):
    cell.config["n_samples"] = 1600
    cell.traffic["rate"] = 2000
    cell.traffic["check_windows"] = 512
    cell.traffic["route"] = "ref"


@pytest.mark.parametrize("workload,shrink,program", [
    ("mlp_unsw.sweep_dp", _sweep, False),
    ("ssm_road.serve_open", _serve, True)])
def test_control_fails_the_limits(workload, shrink, program, bench_env):
    out = control.readings(workload, [21], program, cell_hook=shrink,
                           require_chip=False, root=bench_env)
    limits = common.limits(workload)
    ctl = next(o for o in out if o["side"] == "control")
    assert any(ctl[k] > lim for k, lim in limits.items()), ctl
    for o in out:
        if o["side"] == "program":
            assert all(o[k] <= lim for k, lim in limits.items()), o
