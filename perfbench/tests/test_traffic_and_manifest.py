"""The traffic generator's determinism and clipping, and the manifest's
names, units and files against the contract's rules."""

import collections
import json
import pathlib
import re

import pytest

from perfbench import trafficgen

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def traffic(name):
    return json.load(open(HERE / "traffic" / f"{name}.json"))


@pytest.mark.parametrize("mix", ["chat_steady", "chat_backlog"])
def test_requests_are_deterministic_in_the_seed_and_clipped(mix):
    t = traffic(mix)
    a = trafficgen.requests(t, 20.0, 12345, 32768)
    b = trafficgen.requests(t, 20.0, 12345, 32768)
    c = trafficgen.requests(t, 20.0, 2**31 + 17, 32768)
    assert a == b and a != c
    for r in a + c:
        assert t["prompt_tokens"]["lo"] <= len(r["tokens"]) <= t["prompt_tokens"]["hi"]
        assert t["output_tokens"]["lo"] <= r["max_new"] <= t["output_tokens"]["hi"]
        assert 0 <= r["arrival_s"] < 20.0
        assert all(1 <= x < 32768 for x in r["tokens"])
    arr = [r["arrival_s"] for r in a]
    assert arr == sorted(arr)


@pytest.mark.parametrize("mix", ["chat_steady", "chat_backlog"])
def test_every_seed_gets_the_same_schedule_and_other_tokens(mix):
    t = traffic(mix)
    shape = lambda rs: [(r["arrival_s"], len(r["tokens"]), r["max_new"])
                        for r in rs]
    a = trafficgen.requests(t, 30.0, 1, 32768)
    b = trafficgen.requests(t, 30.0, 2, 32768)
    assert shape(a) == shape(b)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]


def test_a_backlog_offers_the_same_pairs_in_every_pass():
    t = traffic("chat_backlog")
    work = lambda rs: collections.Counter(
        (len(r["tokens"]), r["max_new"]) for r in rs)
    a = trafficgen.requests(t, 30.0, 1, 32768)
    c = t["cycle"]
    assert len(a) > 3 * c
    for k in range(1, 3):
        assert work(a[k * c:(k + 1) * c]) == work(a[:c])
        assert a[k * c:(k + 1) * c] != a[:c]


def test_steady_rate_is_the_files_number():
    t = traffic("chat_steady")
    n = len(trafficgen.requests(t, 200.0, 5, 32768))
    assert n == pytest.approx(t["rate_rps"] * 200.0, rel=0.15)


def test_backlog_ramp_bounds_the_first_waves():
    t = traffic("chat_backlog")
    rs = trafficgen.requests(t, 10.0, 3, 32768)
    ramp = t["ramp"]
    assert rs[0]["arrival_s"] == 0.0
    assert rs[5]["arrival_s"] == pytest.approx(5 * ramp["gap_s"])
    assert {r["arrival_s"] for r in rs[ramp["requests"]:]} == {
        ramp["requests"] * ramp["gap_s"]}


def test_manifest_names_units_and_files():
    m = json.load(open(ROOT / "BENCHMARK.json"))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for group in (m["configs"], m["workloads"], m["end_to_end"], m["per_layer"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for c in m["configs"]:
        assert (ROOT / c["file"]).exists() and c["file"].startswith("perfbench/")
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        body = json.load(open(ROOT / c["file"]))
        assert set(c["reduced"]) == set(body["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in m["workloads"]:
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert (HERE / "workloads" / f"{w['name']}.json").exists()
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in m["end_to_end"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert 0 < e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace")
        assert all(w in cells for w in e.get("workloads", []))
    for p in m["per_layer"]:
        assert UNIT.match(p["unit"]) and p["better"] in ("lower", "higher")
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert p["moves"] in e2e and "bound" not in p
        spec = json.load(open(HERE / "layer_metrics" / f"{p['name']}.json"))
        assert (HERE / "readers" / f"{spec['reader']}.py").exists()
        moved = e2e[p["moves"]]
        for w in p.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (p["name"], w)
    for name in cells:     # every cell: setup_s + another e2e + a per-layer
        assert any(name in e.get("workloads", [name]) for e in m["end_to_end"]
                   if e["name"] != "setup_s")
        assert any(name in p.get("workloads", [name]) for p in m["per_layer"])
