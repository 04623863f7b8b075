"""The cells at sizes a CPU test holds, and cache resets."""


def shrink_sweep(cell):
    """mlp_unsw.sweep_dp at a size a CPU test holds."""
    cell.config["n_samples"] = 2400
    cell.config["fl"]["n_clients"] = 6
    cell.traffic["rounds"] = 20
    cell.traffic["grid"] = [0.1, 10.0]


def shrink_population(cell):
    """mlp_unsw.population_100k at a size a CPU test holds."""
    cell.config["n_samples"] = 3000
    cell.traffic["n_clients"] = 1000
    cell.traffic["rounds"] = 20


def shrink_serve(cell):
    """ssm_road.serve_open at a size a CPU test holds (the kernel route
    needs a TPU; the CPU scores through the reference route)."""
    cell.config["n_samples"] = 1600
    cell.traffic["rate"] = 2000
    cell.traffic["check_windows"] = 256
    cell.traffic["route"] = "ref"


def clear_program_caches():
    """A broken program must be traced anew, not served from the caches."""
    from repro.serve import engine
    from repro.train import fl_driver
    fl_driver._RUNNER_CACHE.clear()
    fl_driver._STACK_CACHE.clear()
    engine._SCORER_CACHE.clear()
