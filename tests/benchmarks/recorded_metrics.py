"""Which per-layer metric files name the recording they are checked on
(their ``recorded`` key: a dump under ``data/``)."""
import os

from bench_paths import BENCH
from harness import reducers


def own_recording():
    """{metric name: its file} for the files that name a recording."""
    files = reducers.load_metric_files(os.path.join(BENCH, "layer_metrics"))
    return {name: m for name, m in files.items() if "recorded" in m}
