"""The checker that every run passes its last line through."""

import copy

import pytest

import contract

CELL = "impala_nature.anakin"


@pytest.fixture()
def bench(bench):
    """The benchmark with a kernel's roofline share added as an entry
    (BENCHMARK.json lists none: PERF.md, Open questions), so that the
    105 % rule is checked on a `_roofline` name too."""
    b = copy.deepcopy(bench)
    b["per_layer"].append({
        "name": "vtrace_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels (ops/pallas/vtrace.py)",
        "moves": "frames_learned_per_s", "workloads": [CELL]})
    return b


def good_line(bench, traced):
    metrics = {name: {"value": 1.5, "unit": m["unit"]}
               for name, m in contract.cell_metrics(bench, CELL, traced).items()}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123456789}
    line = {"correct": True, "attempted": 300, "failed": 0,
            "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=0.4, window_s=4.0)
        line["breakdown"] = {"device_ops": [["fusion.1", 0.25]],
                             "idle_gaps": [["dequeue", 3.1]]}
    return line


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(bench, traced):
    line = good_line(bench, traced)
    assert contract.check_line(line, bench, CELL, traced, chips=1) is line


def _edit(path, value):
    def apply(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        if value is _DELETE:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return apply


_DELETE = object()
BAD = {
    "no correct": (False, _edit(["correct"], _DELETE)),
    "correct not bool": (False, _edit(["correct"], "yes")),
    "attempted negative": (False, _edit(["attempted"], -1)),
    "failed not int": (False, _edit(["failed"], 0.5)),
    "no metrics": (False, _edit(["metrics"], _DELETE)),
    "a metric missing": (False, _edit(["metrics", "setup_s"], _DELETE)),
    "metric is a bare number": (False, _edit(["metrics", "setup_s"], 41.0)),
    "metric is NaN": (False, _edit(["metrics", "setup_s", "value"], float("nan"))),
    "metric is None": (False, _edit(["metrics", "setup_s", "value"], None)),
    "unit differs": (False, _edit(["metrics", "setup_s", "unit"], "ms")),
    "setup_s is zero": (False, _edit(["metrics", "setup_s", "value"], 0.0)),
    "foreign metric": (False, _edit(["metrics", "ttft_p95_ms"],
                                    {"value": 1.0, "unit": "ms"})),
    "extra key": (False, _edit(["notes"], "hello")),
    "no device": (False, _edit(["device"], _DELETE)),
    "no memory peak": (False, _edit(["device", "memory_peak_bytes"], _DELETE)),
    "wrong chip count": (False, _edit(["device", "count"], 4)),
    "breakdown untraced": (False, _edit(["breakdown"], {"device_ops": []})),
    "traced without busy_s": (True, _edit(["device", "busy_s"], _DELETE)),
    "traced without window_s": (True, _edit(["device", "window_s"], _DELETE)),
    "busy_s above window_s": (True, _edit(["device", "busy_s"], 4.5)),
    "busy_s zero": (True, _edit(["device", "busy_s"], 0.0)),
    "mfu above 105": (True, _edit(["metrics", "learn_mfu", "value"], 106.0)),
    "roofline above 105": (True, _edit(["metrics", "vtrace_roofline", "value"], 140.0)),
    "breakdown too long": (True, _edit(["breakdown", "device_ops"],
                                       [["op", 0.1]] * 11)),
    "breakdown row shape": (True, _edit(["breakdown", "idle_gaps"],
                                        [["dequeue", "long"]])),
    "breakdown extra key": (True, _edit(["breakdown", "host"], [])),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_bad_line_is_refused(bench, case):
    traced, apply = BAD[case]
    line = copy.deepcopy(good_line(bench, traced))
    apply(line)
    with pytest.raises(contract.ContractError):
        contract.check_line(line, bench, CELL, traced, chips=1)


def test_unit_of_17_characters_is_refused(bench):
    b = copy.deepcopy(bench)
    unit = "frames/s/per/chip"
    assert len(unit) == 17
    next(m for m in b["end_to_end"] if m["name"] == "setup_s")["unit"] = unit
    line = good_line(b, False)
    assert line["metrics"]["setup_s"]["unit"] == unit
    with pytest.raises(contract.ContractError, match="1 to 16"):
        contract.check_line(line, b, CELL, False, chips=1)


def test_unknown_cell_is_refused(bench):
    with pytest.raises(contract.ContractError):
        contract.cell_metrics(bench, "no_such.cell", False)


def test_every_cell_reports_what_its_layer_metrics_move(bench):
    """A per-layer metric's `moves` is reported in every cell it is in."""
    for w in bench["workloads"]:
        e2e = contract.cell_metrics(bench, w["name"], False)
        layer = contract.cell_metrics(bench, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for name, m in layer.items():
            assert m["moves"] in e2e, (w["name"], name, m["moves"])
