"""`grid_device_ms.tune`: device time of the grid program (the jitted
``_sweep_grid*`` / ``_arrival_grid*`` modules of ``core/sweep.py``)
per execution, in milliseconds, from the trace."""
from bench.metrics._device import grid_device_ms


def read(r: dict):
    return grid_device_ms(r)
