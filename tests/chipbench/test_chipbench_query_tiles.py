"""``attn_query_tile_share.tok`` (PR 43): ONE entry appended behind everything
the benchmark had, a data file for the ``summary_key`` reader that was there,
no reader of its own. On the CPU: no chip, no network, no topology."""
import json

import pytest

from chipbench import spec
from chipbench.readers import summary_key

NAME = "attn_query_tile_share.tok"
CELL = "gpt2-large.decode"


def test_the_entry_is_appended_with_its_file_and_the_reader_that_was_there():
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    # behind PR 41's last (an entry put in the middle reads as a change to
    # what was there); NOT held to be the last, so a later PR can append
    assert names.count(NAME) == 1
    assert names[names.index(NAME) - 1] == "lcf_expert_held_share.tok"
    m = bench["per_layer"][names.index(NAME)]
    assert m == {"name": NAME, "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "out_tok_s", "workloads": [CELL]}
    # the layer is one the benchmark already names, letter for letter
    assert m["layer"] in {e["layer"] for e in bench["per_layer"]
                          if e["name"] != NAME}
    how = spec.load_json("chipbench", "layer_metrics", NAME + ".json")
    assert how == {"reader": "summary_key",
                   "args": {"key": "attn_query_tile_share", "scale": 100.0}}
    json.dumps(how)
    # the one cell whose window holds mixed steps reports it, no other does
    for w in bench["workloads"]:
        reported = {e["name"] for e in
                    spec.metrics_of(bench, w["name"], "per_layer")}
        assert (NAME in reported) == (w["name"] == CELL), w["name"]
    assert "out_tok_s" in {e["name"] for e in
                           spec.metrics_of(bench, CELL, "end_to_end")}
    from tests.chipbench.test_chipbench_arith import \
        test_benchmark_json_names_units_and_files as unchanged
    unchanged()


def test_it_reads_the_window_summary_and_nothing_of_a_parent():
    how = spec.load_json("chipbench", "layer_metrics", NAME + ".json")
    # 15 decode rows at 1 tile and one chunk row at 8, of 16 x 8
    assert summary_key.read({"summary": {"attn_query_tile_share": 23 / 128}},
                            **how["args"]) == pytest.approx(17.96875)
    assert summary_key.read({"summary": {"attn_query_tile_share": 1.0}},
                            **how["args"]) == 100.0
    # the parent's summary has no such key, and neither has a window with no
    # paged step wider than a tile: nothing, and no error
    assert summary_key.read({"summary": {"steps": 3}}, **how["args"]) is None
    assert summary_key.read({}, **how["args"]) is None
