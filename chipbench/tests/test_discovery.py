"""Configurations, traffic mixes, limits and metric readers are found by
the names in ``BENCHMARK.json``: adding one is adding its files."""

import json
import shutil

import pytest

from chipbench import check, families, spec


def bench():
    return json.loads(spec.BENCHMARK.read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    w = {w["name"]: w for w in bench()["workloads"]}[cell]
    assert c.config["name"] == w["config"]
    assert c.traffic["name"] == w["traffic"]
    assert {m.name for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer and all(callable(m.read) for m in c.per_layer)
    assert set(c.limits) == {"gap_accurate", "gap_fast"}


def test_every_metric_has_a_reader():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_config_files_are_the_declared_ones():
    for c in bench()["configs"]:
        data = json.loads((spec.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_new_files_and_an_entry_make_a_new_cell(tmp_path):
    """A configuration, a mix and a metric added as files only."""
    base = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((base / "configs" / "internlm2-1.8b.json").read_text())
    cfg.update(name="internlm2-half", num_hidden_layers=12)
    (base / "configs" / "internlm2-half.json").write_text(json.dumps(cfg))
    (base / "traffic" / "long.json").write_text(json.dumps(
        {"name": "long", "batch": 2, "prompt_len": 8192, "new_tokens": 64,
         "schedule": [["accurate", 64]]}))
    (base / "limits" / "internlm2-half.long.json").write_text(json.dumps(
        {"gap_accurate": {"limit": 1.0}, "gap_fast": {"limit": 1.0}}))
    (base / "metrics" / "calls.py").write_text(
        "def read(rec):\n    return len(rec.calls)\n")
    b = bench()
    b["workloads"].append({"name": "internlm2-half.long",
                           "config": "internlm2-half", "traffic": "long",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "calls", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "x", "moves": "tok_s",
                           "workloads": ["internlm2-half.long"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.load_cell("internlm2-half.long", bench=tmp_path / "BENCHMARK.json",
                       base=base)
    assert c.config["num_hidden_layers"] == 12
    assert c.traffic["prompt_len"] == 8192
    assert [m.name for m in c.per_layer] == ["calls"]
    assert c.per_layer[0].read(type("R", (), {"calls": [1, 2]})()) == 2


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")


def test_family_is_found_by_name(tmp_path, monkeypatch):
    """A family is its module, ``families/<family>.py``."""
    cfg = json.loads((spec.HERE / "configs" / "internlm2-1.8b.json")
                     .read_text())
    assert type(families.arch(cfg)).__module__ == "chipbench.families.dense"
    pkg = tmp_path / "fams"
    pkg.mkdir()
    (pkg / "toy.py").write_text(
        "class Arch:\n"
        "    @classmethod\n"
        "    def from_config(cls, c):\n"
        "        return (cls, c['name'])\n")
    monkeypatch.setattr(families, "__path__", [str(pkg)])
    monkeypatch.delitem(__import__("sys").modules,
                        "chipbench.families.toy", raising=False)
    got = families.arch({"name": "t", "family": "toy"})
    assert got[1] == "t" and got[0].__module__ == "chipbench.families.toy"
    with pytest.raises(ValueError):
        families.arch({"name": "t", "family": "no_such_family"})


@pytest.mark.parametrize("mix", ["rag", "chat"])
def test_schedule_is_stated_by_the_mix(mix):
    t = json.loads((spec.HERE / "traffic" / f"{mix}.json").read_text())
    rungs = check.stated_rungs(t)
    assert len(rungs) == t["new_tokens"] == 64
    assert rungs.count("accurate") == 43 and rungs.count("fast") == 21
    assert check.replayed(rungs) == 42
    with pytest.raises(ValueError):
        check.stated_rungs(dict(t, new_tokens=65))
