"""Tests of the benchmark's own harness: `python -m pytest perfbench/tests -q`.

Nothing here touches JAX at import. The tests that need it import it
inside the test, on the CPU (`JAX_PLATFORMS=cpu`)."""

import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DATA_DIRS = ("modes", "reducers", "layer_metrics", "traffic", "workloads",
             "configs", "families", "torsos")


@pytest.fixture(scope="session")
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny_sections() -> dict:
    with open(os.path.join(ROOT, "config.json")) as f:
        root = json.load(f)
    imp = dict(root["impala_cartpole"], algorithm="impala", num_actors=2,
               envs_per_actor=8)
    imp["env"] = imp["env"] * 2
    imp["available_action"] = imp["available_action"] * 2
    r2d2 = dict(root["r2d2"], algorithm="r2d2", batch_size=8, lstm_size=64,
                priority_eta=0.9)
    return {"impala_tiny": imp, "r2d2_tiny": r2d2}


@pytest.fixture(scope="session")
def tiny_sections() -> dict:
    return _tiny_sections()


@pytest.fixture()
def data_copy(tmp_path, bench):
    """A temporary copy of the benchmark's data directories with
    CartPole-sized configurations and cells ADDED as new files — no file
    of the copy is edited — and a BENCHMARK.json that lists them."""
    dd = str(tmp_path / "data")
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(BENCH_DIR, d), os.path.join(dd, d))
    before = {d: sorted(os.listdir(os.path.join(dd, d))) for d in DATA_DIRS}
    secs = _tiny_sections()

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    imp, r2 = secs["impala_tiny"], secs["r2d2_tiny"]
    dump("configs/tiny_impala.json", {
        "name": "tiny_impala", "section": "impala_tiny", "kernels": {},
        "frames_per_update": imp["batch_size"] * imp["trajectory"],
        "impala_tiny": imp})
    dump("configs/tiny_r2d2.json", {
        "name": "tiny_r2d2", "section": "r2d2_tiny", "kernels": {},
        "frames_per_update": 8 * (r2["seq_len"] - r2["burn_in"]),
        "r2d2_tiny": r2})
    quick = {"warm_updates": 3, "trace_start_s": 0.3, "trace_seconds": 1.0,
             "stats_s": 0.25, "env": {"DRL_TELEMETRY_FLUSH_S": "0.25"}}
    dump("workloads/tiny_impala.hostloop.json",
         {"config": "tiny_impala", "traffic": "hostloop", "overrides": quick})
    dump("workloads/tiny_impala.anakin.json",
         {"config": "tiny_impala", "traffic": "anakin",
          "overrides": {"num_envs": 8, "chunk_updates": 4}})
    dump("workloads/tiny_r2d2.hostloop.json",
         {"config": "tiny_r2d2", "traffic": "hostloop",
          "overrides": {**quick, "env": {"DRL_REPLAY_SPILL": "0",
                                         "DRL_TELEMETRY_FLUSH_S": "0.25"}}})
    # a per-layer metric over another span, with arithmetic of its own
    dump("layer_metrics/ingest_ms.json", {
        "name": "ingest_ms", "unit": "ms", "reducer": "span_total_ms",
        "source_detail": {"stages": ["ingest_dequeue"]}})
    with open(os.path.join(dd, "reducers", "span_total_ms.py"), "w") as f:
        f.write("def reduce(facts, spec):\n"
                "    return facts.get('span_total_ms', {}).get(\n"
                "        spec['source_detail']['stages'][0])\n")
    # a third family and a torso that is not the Nature stack, as files
    # only: `toy` runs the IMPALA learner under another name and counts
    # its own operations over the `slab` torso
    with open(os.path.join(dd, "families", "toy.py"), "w") as f:
        f.write("import discover, os\n"
                "_imp = discover.module(os.path.dirname(os.path.dirname(\n"
                "    os.path.abspath(__file__))), 'families', 'impala')\n"
                "LAUNCHER, LOSS_TAG = _imp.LAUNCHER, _imp.LOSS_TAG\n"
                "UPDATE_METHOD = _imp.UPDATE_METHOD\n"
                "reference_check = _imp.reference_check\n"
                "learn_step_kernels = _imp.learn_step_kernels\n"
                "def learn_flops_per_update(section, torso, batch=None):\n"
                "    macs, features = torso\n"
                "    return 3 * 2 * (macs + features) * (\n"
                "        batch or section['batch_size'])\n")
    with open(os.path.join(dd, "torsos", "slab.py"), "w") as f:
        f.write("def macs(section):\n"
                "    h, w, c = section['model_input']\n"
                "    return h * w * c * 16, 16\n")
    host = ["tiny_impala.hostloop", "tiny_r2d2.hostloop"]
    impala = ["tiny_impala.hostloop", "tiny_impala.anakin"]
    b = json.loads(json.dumps(bench))
    # the host loop's metrics come in as added ENTRIES, as a later PR
    # would add them (fixtures/hostloop_entries.json says why they wait)
    with open(os.path.join(BENCH_DIR, "tests", "fixtures",
                           "hostloop_entries.json")) as f:
        dormant = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for m in dormant[group]:
            b[group].append({**m, "workloads": host})
    b["workloads"] = [
        {"name": n, "config": n.split(".")[0], "traffic": n.split(".")[1],
         "chips": 1, "why": "test"} for n in host + impala[1:]]
    # no published peaks for a CPU: the rehearsal reads no utilization
    b["per_layer"] = [m for m in b["per_layer"]
                      if m["name"] not in ("learn_mfu", "vtrace_roofline")]
    b["per_layer"].append({
        "name": "ingest_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Data plane",
        "moves": "frames_learned_per_s", "workloads": ["tiny_r2d2.hostloop"]})
    bench_path = os.path.join(dd, "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(b, f)
    for d in DATA_DIRS:  # files were added, none was edited or removed
        assert set(before[d]) <= set(os.listdir(os.path.join(dd, d)))
    return {"dir": dd, "benchmark": bench_path, "bench": b}
