"""Reference for ``-o feature-count`` over the ``bulk`` commit of the
builder ``int_pk_churn_layer`` (one contiguous run of pks deleted, updates
elsewhere): ``feature_count_churn``'s two count checks against that commit's
edit sets. Whether the windowed join overflowed is **not** checked either
way: today the run sends the call to the sort-join, a later PR may keep it
on the windowed path, and both answers are right; the extent is a metric
(``join.overflow_tiles``)."""

import importlib.util
import os


def _churn_reference():
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "feature_count_churn.py"
    )
    spec = importlib.util.spec_from_file_location("bench_references_churn", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check(output, info):
    """-> {check name: bool} for the command's output bytes."""
    return _churn_reference().check_commit(output, info, "bulk")
