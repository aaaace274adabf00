"""A metric file may name the recording it is checked on.

``test_perfbench_reducers.py`` holds one hand-written expectation per
serving metric on ``data/engine_sources_tiny.json``, which was recorded
before the engine had a device lane or an edge span.  A metric file
with a ``recorded`` key names another dump under ``data/``;
``test_perfbench_recorded.py`` checks it there, and its case of the
older test, which could read nothing, is left out here."""
from recorded_metrics import own_recording

OLDER = "test_serving_metric_files_on_the_recorded_trace"


def pytest_collection_modifyitems(config, items):
    own = own_recording()
    drop = [it for it in items
            if getattr(it, "originalname", None) == OLDER
            and it.callspec.params.get("name") in own]
    if drop:
        config.hook.pytest_deselected(items=drop)
        items[:] = [it for it in items if it not in drop]
