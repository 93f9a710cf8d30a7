"""Every configuration, mix, reference and metric reader is found by its
name in BENCHMARK.json, and a new one is added as files and entries."""
import json
import re
import shutil

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_pieces_load_by_name(cell):
    w = spec.workload(BENCH, cell)
    cfg = spec.config(BENCH, w["config"])
    mix = spec.traffic(w["traffic"])
    model = spec.model_module(cfg["family"])
    assert model.layers(cfg)
    assert mix["loop"] in ("closed", "open")
    assert w["chips"] == 1
    e2e = {m["name"] for m in spec.end_to_end_metrics(BENCH, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer_metrics(BENCH, cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(spec.metric_reader(m["name"]))


def test_benchmark_names_and_units():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x for x in layers)


def test_readers_return_none_on_an_empty_run():
    rec = {"spans": None, "device_trace": None, "fold": None, "peak": {},
           "flops_per_image": 1.0}
    for m in BENCH["per_layer"]:
        assert spec.metric_reader(m["name"])(rec) is None, m["name"]


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        spec.model_module("no_such_family")


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A copy of the benchmark gains a configuration, a mix, a metric
    reader and their entries; nothing already there is edited."""
    shutil.copytree(spec.ROOT / "chipbench", tmp_path / "chipbench")
    before = {p: p.read_bytes() for p in
              (tmp_path / "chipbench").rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    models = tmp_path / "chipbench" / "models"
    (models / "vgg16half.py").write_text(
        (models / "vgg16.py").read_text().replace("BIAS_STD = 0.1",
                                                  "BIAS_STD = 0.05"))
    cfg = spec.config(BENCH, "vgg16-224")
    cfg.update(name="vgg16-160", img=160, family="vgg16half")
    (tmp_path / "chipbench" / "configs" / "vgg16-160.json").write_text(
        json.dumps(cfg))
    mix = spec.traffic("vgg16-224.bulk")
    mix["clients"] = 4
    (tmp_path / "chipbench" / "traffic" / "vgg16-160.bulk.json"
     ).write_text(json.dumps(mix))
    (tmp_path / "chipbench" / "metrics" / "requests_per_batch.py"
     ).write_text("def read(rec):\n    return 4.0\n")
    bench["configs"].append({"name": "vgg16-160", "source": "x",
                             "file": "chipbench/configs/vgg16-160.json",
                             "reduced": ["img"], "why": "x"})
    bench["workloads"].append({"name": "vgg16-160.bulk",
                               "config": "vgg16-160",
                               "traffic": "vgg16-160.bulk", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][0]["workloads"].append("vgg16-160.bulk")
    bench["per_layer"].append({"name": "requests_per_batch", "unit": "1",
                               "better": "higher", "source": "program_span",
                               "layer": "batching", "moves": "images_per_s",
                               "workloads": ["vgg16-160.bulk"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    b = spec.load_benchmark(tmp_path)
    w = spec.workload(b, "vgg16-160.bulk")
    c = spec.config(b, w["config"], tmp_path)
    assert c["img"] == 160
    assert spec.traffic(w["traffic"], tmp_path)["clients"] == 4
    model = spec.model_module(c["family"], tmp_path)
    assert model.BIAS_STD == 0.05 and model.layers(c)[0]["h"] == 160
    names = [m["name"] for m in spec.per_layer_metrics(b, w["name"])]
    assert "requests_per_batch" in names
    assert spec.metric_reader("requests_per_batch", tmp_path)({}) == 4.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
