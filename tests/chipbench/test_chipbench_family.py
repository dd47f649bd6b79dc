"""The harness names no family (PR 26): a configuration of ANOTHER family's
key names, cut to a chip's share, becomes a cell by files and entries alone;
a cut that is not written down is refused; and the roofline readers find
their kernel by its name. On the CPU: no chip, no network, no topology call.
The other family is a stand-in (``other_family/latent.py``): the only
program a CPU test can serve is the tiny GPT-2."""
import copy
import importlib.util
import os
import sys
import types

import pytest

from chipbench import run as bench_run
from chipbench import spec
from chipbench.readers import trace_roofline
from chipbench.reduce import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
FAMILY = os.path.join(HERE, "other_family")
CONFIG = "tests/chipbench/other_family/latent-cut-serve.json"
CELL = "latent-cut.decode"


def _reference():
    """``other_family/latent.py`` as the module ``chipbench.reference.latent``
    (where ``spec.plugin`` looks), without a file under ``chipbench/``."""
    s = importlib.util.spec_from_file_location(
        "chipbench.reference.latent", os.path.join(FAMILY, "latent.py"))
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def _entry():
    cfg = spec.load_json(CONFIG)
    return {"name": "latent-cut-serve", "source": "a stand-in: no model",
            "file": CONFIG, "reduced": list(cfg["reduced"]), "why": "-"}


@pytest.fixture
def as_a_cell(monkeypatch):
    """The entries a PR would append to BENCHMARK.json, and nothing else."""
    monkeypatch.setitem(sys.modules, "chipbench.reference.latent",
                        _reference())
    bench = spec.benchmark()
    bench["configs"].append(_entry())
    bench["workloads"].append({
        "name": CELL, "config": "latent-cut-serve", "traffic": "decode",
        "chips": 1, "why": "-"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2-large.decode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    return bench


def _run(seed):
    return bench_run.run_cell(bench_run.parse(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "3",
         "--trace", "0", "--rehearse"]))


def test_another_familys_cut_configuration_is_a_cell_by_files_and_entries(
        as_a_cell, monkeypatch):
    latent = sys.modules["chipbench.reference.latent"]
    built = []
    real = latent.Forward
    monkeypatch.setattr(latent, "Forward", lambda p, sz, length, quant=None:
                        built.append(length) or real(p, sz, length, quant))
    result, obs = _run(2 ** 31 + 91)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 16
    sz = obs["sizes"]
    assert sz["kv_lora_rank"] == 512 and "n_layer" not in sz
    assert (sz["vocab_size"], sz["positions"]) == (50257, 128)
    # the reference was built for the longest sampled request, rounded up as
    # ITS module asks (32s), not for the 1,024 positions the file declares
    longest = max(len(r.tokens) + len(r.streamed)
                  for r in obs["client"].reqs.values() if r.streamed)
    assert built and set(built) <= {-(-longest // 32) * 32} and \
        built[0] < sz["table"]
    # every number compared is in the result line, last, beside its limit
    assert list(result)[-1] == "checks"
    assert result["checks"]["gap_mean"]["limit"] == 0.0001
    assert all(c["ok"] for c in result["checks"].values())
    # a request the configuration's positions cannot hold is the mix's fault
    from chipbench.drivers.serve_stdin import Req

    with pytest.raises(ValueError, match="128 positions"):
        obs["client"].send(Req("long", [1] * 100, 29, None))


def test_an_altered_token_makes_the_other_familys_run_incorrect(
        as_a_cell, monkeypatch):
    from tnn_tpu.serving.supervisor import EngineSupervisor

    real = EngineSupervisor._emit

    def emit(self, rid, ev):
        if ev.get("event") == "token":      # altered where it is produced
            ev = dict(ev, token=(int(ev["token"]) + 1) % 50257)
        return real(self, rid, ev)

    monkeypatch.setattr(EngineSupervisor, "_emit", emit)
    result, obs = _run(2 ** 31 + 92)
    assert result["correct"] is False
    assert result["checks"]["gap_max"]["ok"] is False
    assert obs["readings"]["gap_max"] > \
        obs["ctx"].config["rehearsal"]["limits"]["gap_max"]


def test_a_program_that_is_not_the_configurations_is_refused():
    latent, cfg = _reference(), spec.load_json(CONFIG)
    sz = latent.sizes_of(cfg["rehearsal"])
    model = types.SimpleNamespace(num_layers=2, d_model=128, num_heads=2,
                                  vocab_size=50257, max_len=1024)
    latent.check_program(model, sz, "gpt2_tiny")
    model.num_layers = 3
    with pytest.raises(SystemExit, match="the program's gpt2_tiny has sizes"):
        latent.check_program(model, sz, "gpt2_tiny")
    from chipbench.reference import gpt2

    large = gpt2.sizes_of(spec.load_json(
        "chipbench", "configs", "gpt2-large-serve.json"))
    model = types.SimpleNamespace(num_layers=36, d_model=1280, num_heads=20,
                                  vocab_size=50257, max_len=1024)
    gpt2.check_program(model, large, "gpt2_large")
    model.d_model = 1024
    with pytest.raises(SystemExit, match="the configuration file says"):
        gpt2.check_program(model, large, "gpt2_large")
    assert (large["vocab_size"], large["positions"]) == (50257, 1024)
    assert gpt2.forward_length(large, 797) == 896
    assert gpt2.forward_length(large, 1020) == 1024


# ------------------------------------------------------------ the schema ----

def _drop(key):
    def change(cfg, entry):
        del cfg[key]
    return change


def _width(cfg, entry):
    cfg["reduced"].append("kv_lora_rank")
    entry["reduced"].append("kv_lora_rank")
    cfg["published"]["kv_lora_rank"] = 512


def _any_dim(cfg, entry):           # no module need declare a *_dim
    cfg["reduced"].append("head_dim")
    entry["reduced"].append("head_dim")
    cfg.update(head_dim=64)
    cfg["published"]["head_dim"] = 128


def _unpublished(cfg, entry):
    del cfg["published"]["vocab_size"]


def _not_a_key(cfg, entry):
    cfg["reduced"].append("n_shared_experts")
    entry["reduced"].append("n_shared_experts")
    cfg["published"]["n_shared_experts"] = 1


def _entry_differs(cfg, entry):
    entry["reduced"] = entry["reduced"][:-1]


def _deployment_in_prose(cfg, entry):
    cfg["deployment"] = "one chip of sixteen"


@pytest.mark.parametrize("change,complaint", [
    (None, None),
    (_width, "names the width 'kv_lora_rank'"),
    (_any_dim, "names the width 'head_dim'"),
    (_unpublished, "'published' lacks the source's value of 'vocab_size'"),
    (_drop("published"), "'published' object"),
    (_drop("deployment"), "'deployment' is an object"),
    (_deployment_in_prose, "'deployment' is an object"),
    (_not_a_key, "no top-level key"),
    (_entry_differs, "in BENCHMARK.json"),
])
def test_a_cut_has_to_be_written_down(change, complaint):
    cfg, entry, latent = spec.load_json(CONFIG), _entry(), _reference()
    if change is None:
        assert cfg["reduced"] and spec.check_cut(entry, cfg, latent) is None
        return
    change(cfg, entry)
    with pytest.raises(ValueError, match=complaint):
        spec.check_cut(entry, cfg, latent)


def test_an_uncut_configuration_needs_none_of_it():
    for c in spec.benchmark()["configs"]:
        cfg = spec.load_json(c["file"])
        assert cfg["reduced"] == [] and "published" not in cfg
        spec.check_cut(c, cfg, spec.plugin("reference", cfg["reference"]))


# ------------------------------------- roofline readers, by the kernel's name ----

REDUCE = os.path.join(spec.ROOT, "chipbench", "reduce")
OTHER_KERNEL = ('%tnn_other_kernel.7 = f32[256,512]{1,0} custom-call(f32[256,'
                '512]{1,0} %x), custom_call_target="tpu_custom_call"')
LARGE = dict(n_layer=36, n_embd=1280, n_head=20, vocab_size=50257,
             n_positions=1024)


def _renamed(path, old, new):
    """A recorded v5e trace whose one kernel carries the program's name."""
    tr = xplane.reduce_file(os.path.join(REDUCE, path))
    assert sum(1 for text, _, _ in tr["ops"]
               if text.startswith(f"%{old}")) == 1
    tr["ops"] = [(f"%{new}" + text[len(old) + 1:]
                  if text.startswith(f"%{old}") else text, s, n)
                 for text, s, n in tr["ops"]]
    return tr


def _serving(tr):
    from chipbench.drivers.serve_stdin import Client, Req

    client = Client.__new__(Client)
    client.reqs = {}
    for i, prompt in enumerate((100, 300)):
        r = Req(f"r{i}", [1] * prompt, 8, 0.0)
        r.token_times = [10.0 + 1e-3 * k for k in range(5)]    # in the slice
        client.reqs[r.id] = r
    # contexts of the decoded tokens 1-4 of each: prompt + i
    contexts = sum(p + i for p in (100, 300) for i in range(1, 5))
    least = contexts * 2 * 36 * 1280 * 2 / 819e9       # bandwidth bound
    return {"trace": tr, "client": client, "sizes": LARGE,
            "device": {"kind": "TPU v5 lite"},
            "ctx": types.SimpleNamespace(
                trace_wall=(10.0, 10.0 + tr["window_s"]))}, least


def _training(tr):
    obs = {"trace": tr, "kind": "train", "sizes": dict(LARGE, n_layer=24,
           n_embd=1024, n_head=16), "device": {"kind": "TPU v5 lite"},
           "steps_in_window": 10, "window_s": 10 * tr["window_s"],
           "batch": 8, "seq": 1024}
    return obs, 24 * 6 * 2 ** 33 / 197e12              # compute bound


@pytest.mark.parametrize("metric,path,old,new,observe,seconds", [
    ("paged_attn_roofline.tok", "sample_v5e_scoped.xplane.pb",
     "tnn_sample_kernel", "tnn_paged_attention", _serving, 3.031e-6),
    ("flash_attn_roofline.train", "sample_v5e.xplane.pb",
     "tiny", "tnn_flash_fwd", _training, 20.017e-6),
])
def test_roofline_readers_find_their_kernel_by_name(metric, path, old, new,
                                                    observe, seconds):
    how = spec.load_json("chipbench", "layer_metrics", metric + ".json")
    assert how["reader"] == "trace_roofline"
    obs, least = observe(_renamed(path, old, new))
    alone = trace_roofline.read(obs, **how["args"])
    assert alone == pytest.approx(100 * least / seconds, rel=1e-3)
    # a second Pallas kernel of another name in the slice changes nothing ...
    crowded = copy.deepcopy(obs)
    crowded["trace"]["ops"].append((OTHER_KERNEL, 5e-6, 3))
    assert trace_roofline.read(crowded, **how["args"]) == alone
    # ... and is found by its own ("every custom call of the program", PR
    # 23's pattern, would have taken it for the first)
    assert trace_roofline.kernel_seconds(
        crowded["trace"], "^tnn_other_kernel") == pytest.approx(5e-6)
    assert "custom_call_target" in OTHER_KERNEL
    # the recorded trace as it is has no kernel of that name: nothing to read
    bare, _ = observe(xplane.reduce_file(os.path.join(REDUCE, path)))
    assert trace_roofline.read(bare, **how["args"]) is None
