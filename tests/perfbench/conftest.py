"""The one comparison of the benchmark's own tests that only a
`benchmark` PR may mend (a PR of another kind may add files here and edit
none), tolerated at that line and nowhere else.

``test_a_family_of_other_keys_comes_in_as_files_alone`` ends by comparing
the first TWO configurations and cells of its copy with ALL of the
checkout's (``now["configs"][:2] == untouched["configs"]``): true while
the benchmark had two of each, false from the first configuration a
``model_config`` PR adds (PR 29), whatever that configuration is.  What
it means to hold is ``now[...][:len(untouched[...])] == untouched[...]``.

So the test runs as written.  Every assertion before that line has to
pass (the added family ``correct`` plain and traced, its counts against
the hand counts, no existing file edited): a failure anywhere else, or
of another kind, is reported as the failure it is.  At that line the
comparison it means, and the one of the cells after it, are made here
with the test's own ``now`` and ``untouched``, and only then is the case
reported as an expected failure.  A case that passes as written fails
here, so that the `benchmark` PR which mends the line (PERF.md section 7)
deletes this file with it.
"""
import linecache

import pytest

PINNED_AT_TWO = "test_a_family_of_other_keys_comes_in_as_files_alone"
THE_LINE = 'assert now["configs"][:2] == untouched["configs"]'


def _frame_of_the_test(tb):
    while tb is not None:
        if tb.tb_frame.f_code.co_name == PINNED_AT_TWO:
            return tb
        tb = tb.tb_next
    return None


@pytest.hookimpl(wrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    if pyfuncitem.originalname != PINNED_AT_TWO:
        return (yield)
    try:
        yield
    except AssertionError as failure:
        tb = _frame_of_the_test(failure.__traceback__)
        at = tb and linecache.getline(tb.tb_frame.f_code.co_filename,
                                      tb.tb_lineno).strip()
        if at != THE_LINE:
            raise
        now = tb.tb_frame.f_locals["now"]
        untouched = tb.tb_frame.f_locals["untouched"]
    else:
        pytest.fail(f"{PINNED_AT_TWO} passes as written: delete "
                    "tests/perfbench/conftest.py")
    for key in ("configs", "workloads"):
        n = len(untouched[key])
        assert n > 2 and now[key][:n] == untouched[key] and \
            len(now[key]) > n, f"an entry of {key!r} that was there changed"
    pytest.xfail(
        f"every assertion up to `{THE_LINE}` passed, and so did what that "
        "line and the next mean ([:len(untouched[...])]); as written they "
        "compare the first two configurations with all of BENCHMARK.json's, "
        "which has three since PR 29: a `benchmark` PR's edit (PERF.md "
        "section 7)")
