"""cand_ms.tick: device time per tick outside the greedy's program: the
candidate build (gathers, ``qos_candidates``), the host-to-device copies
and everything else the tick runs on the chip."""
import importlib.util
from pathlib import Path


def _greedy():
    path = Path(__file__).with_name("greedy_ms.tick.py")
    spec = importlib.util.spec_from_file_location("greedy_ms_tick", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(run):
    greedy = _greedy().program_ns(run)
    if greedy is None:
        return None
    busy = run.trace.busy_ns(run.trace.devices[0])
    return (busy - greedy) / 1e6 / run.steps
