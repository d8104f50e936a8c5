"""``BENCHMARK.json`` names only what has a file, within its limits."""
import re

import spec as SPEC

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_name_has_its_file():
    b = SPEC.load_json(SPEC.ROOT / "BENCHMARK.json")
    for c in b["configs"]:
        assert (SPEC.ROOT / c["file"]).is_file()
        assert NAME.match(c["name"])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (SPEC.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = SPEC.load_cell(w["name"])
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])
    for m in b["end_to_end"]:
        assert m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                      "device_trace")
    assert e2e["setup_s"]["bound"] <= 0.25
