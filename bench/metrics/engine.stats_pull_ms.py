"""Device idle milliseconds per engine step inside the program's
``host.engine.stats.pull`` spans (every device-to-host transfer of the
step's routing counters): the idle time of ``engine.host_serial_ms``
intersected with those spans, over the same number of steps."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_engine_host_serial_ms_part",
    Path(__file__).with_name("engine.host_serial_ms.py"))
_serial = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_serial)


def read(run):
    return _serial.idle_ms_per_step(run, "host.engine.stats.pull")
