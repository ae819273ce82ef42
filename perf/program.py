"""What every driver asks of the program under test, in one place: its
mesh over the cell's chips, its registry of counters, and letting go of
its state before the reference runs."""

from __future__ import annotations

import gc
import importlib.util


def load_module(name: str, path: str):
    """A module by file path (drivers, metric readers of their own)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def init_mesh(config: dict, traffic: dict, chips: int, devices):
    """``core.init`` over the cell's chips: the traffic mix's mesh where
    it states one, else the configuration's for that many chips."""
    from multiverso_tpu import core
    dp, mp = traffic.get("mesh") or config["mesh"][str(chips)]
    return core.init(devices=list(devices), data_parallel=dp,
                     model_parallel=mp)


def registry_snapshot() -> dict:
    from multiverso_tpu.telemetry import metrics
    return metrics.snapshot()


def free() -> None:
    """Drop the program's tables and mesh; the caller has dropped its
    own references already."""
    from multiverso_tpu import core
    from multiverso_tpu.tables.base import reset_tables
    reset_tables()
    core.shutdown(finalize=False)
    gc.collect()
