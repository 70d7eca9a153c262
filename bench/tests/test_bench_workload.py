"""A configuration's ``network`` entry is the whole workload the
harness prices: ``bench/harness.py`` ``workload_of``."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.fixture
def llm_root(tmp_path):
    """A checkout-shaped directory whose one configuration states a
    sequence length, added by files alone."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    cfg = dict(config("r50-train-ht3"), name="qwen-train",
               network={"net": "qwen3_0_6b", "training": True, "batch": 2,
                        "seq": 4096})
    (tmp_path / "bench" / "configs" / "qwen-train.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "warm-sweep.json").write_text(
        (ROOT / "bench" / "traffic" / "warm-sweep.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "qwen-train", "source": "test",
                         "file": "bench/configs/qwen-train.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "qwen-train.warm-sweep",
                           "config": "qwen-train", "traffic": "warm-sweep",
                           "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_a_configured_sequence_length_reaches_the_workload(llm_root):
    _, _, cfg, _ = harness.load_cell("qwen-train.warm-sweep", llm_root)
    wl = harness.workload_of(cfg)
    assert (wl.net, wl.training, wl.batch, wl.seq) == \
        ("qwen3_0_6b", True, 2, 4096)
    from repro.models.frontends import lower_llm, resolve_llm_config
    head = lower_llm(resolve_llm_config("qwen3_0_6b"), batch=2, seq=4096)[-1]
    assert (head.name, head.m) == ("lm_head", 2 * 4096)
    fwd = [l for l in wl.layers() if getattr(l, "name", "") == "lm_head"]
    assert [l.m for l in fwd] == [8192]


@pytest.mark.parametrize("key", ["seq_len", "sequence", "Seq"])
def test_an_unknown_network_key_raises_and_names_it(key):
    cfg = config("r50-infer-hi3")
    cfg["network"] = dict(cfg["network"], **{key: 4096})
    with pytest.raises(ValueError, match=key):
        harness.workload_of(cfg)


@pytest.mark.parametrize("name", ["r50-infer-hi3", "r50-train-ht3"])
@pytest.mark.parametrize("label", [None, "warmup1"])
def test_resnet_configurations_build_the_same_workload(name, label):
    from repro.core.study import Workload
    cfg = config(name)
    net = cfg["network"]
    before = Workload(net["net"], training=bool(net["training"]),
                      batch=int(net["batch"]), name=label)
    assert harness.workload_of(cfg, label) == before
    assert harness.workload_of(cfg, label).seq is None


def test_a_sequence_length_on_a_cnn_is_refused():
    cfg = config("r50-infer-hi3")
    cfg["network"] = dict(cfg["network"], seq=4096)
    with pytest.raises(ValueError, match="seq"):
        harness.workload_of(cfg).layers()
