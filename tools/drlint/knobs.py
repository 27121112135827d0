"""The DRL_* knob registry: single source of truth for every env gate.

The repo's behavior gates multiplied PR over PR (~60 `DRL_*` reads by
PR 11) with nothing keeping three surfaces coherent: the code that
reads a knob, the docs table that explains it, and the launcher/test
surfaces that set it. This module is the one registry all three hang
off:

- every `DRL_*` string literal in linted source must name an entry
  here (the `knob-registry` drlint pass — an unregistered read is a
  lint failure, so gate drift is caught the commit it happens);
- the `docs/performance.md` knob table is GENERATED from this registry
  (`python -m tools.drlint.knobs --write`); a hand-edit of the table
  or a registry change without a regen is the same lint failure;
- `scan_tree()` round-trips the registry against the tree so a knob
  that stops being read is flagged as stale instead of rotting as
  documentation of nothing.

Entry fields: `type` is the value vocabulary (`flag` on/off as
`utils/environ.env_flag` reads it, `int`, `float`, `str`, `list`
comma-separated, `enum`), `default` is the effective
unset behavior (free text where the default is computed), `owner` is
the module that reads it (repo-relative).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

KNOB_RE = re.compile(r"^DRL_[A-Z][A-Z0-9_]*$")

BEGIN_MARK = ("<!-- BEGIN KNOB REGISTRY (generated by tools/drlint/knobs.py"
              " — edit the registry, then `python -m tools.drlint.knobs"
              " --write`) -->")
END_MARK = "<!-- END KNOB REGISTRY -->"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DOCS_PATH = os.path.join(_REPO_ROOT, "docs", "performance.md")

_TYPES = ("flag", "int", "float", "str", "list", "enum")

# Files whose DRL_* literals are NOT knob references: the registry's
# own entries, and the linter test suite's deliberately-fake fixture
# names. Shared by scan_tree() and the knob-registry lint pass so the
# two can never disagree.
SCAN_EXCLUDE = ("tools/drlint/knobs.py", "tests/test_drlint.py")


@dataclass(frozen=True)
class Knob:
    name: str
    type: str
    default: str
    owner: str
    doc: str

    def __post_init__(self):
        if not KNOB_RE.match(self.name):
            raise ValueError(f"bad knob name {self.name!r}")
        if self.type.split("(")[0] not in _TYPES:
            raise ValueError(f"{self.name}: bad type {self.type!r}")
        if not self.doc or not self.owner:
            raise ValueError(f"{self.name}: owner and doc are required")


_PKG = "distributed_reinforcement_learning_tpu"

_ENTRIES = [
    # -- data planes and fast-path gates ---------------------------------
    Knob("DRL_SHM_RING", "flag", "0",
         f"{_PKG}/runtime/shm_ring.py",
         "co-hosted actors PUT trajectories through a shared-memory SPSC "
         "ring instead of loopback TCP"),
    Knob("DRL_SHM_RING_MB", "float", "64", f"{_PKG}/runtime/shm_ring.py",
         "ring segment size in MB"),
    Knob("DRL_SHM_RING_ATTACH_S", "float", "5",
         f"{_PKG}/runtime/shm_ring.py",
         "actor-side bounded attach retry budget in seconds"),
    Knob("DRL_SHM_RING_CREATE", "list", "unset",
         f"{_PKG}/runtime/transport.py",
         "learner side: comma-separated ring names to create and drain"),
    Knob("DRL_SHM_RING_NAME", "str", "unset", f"{_PKG}/runtime/transport.py",
         "actor side: ring segment name to attach"),
    Knob("DRL_SHM_WEIGHTS", "flag", "1 on x86-64, else 0",
         f"{_PKG}/runtime/weight_board.py",
         "co-hosted actors pull weights from the seqlock shm board"),
    Knob("DRL_SHM_WEIGHTS_MB", "float", "64",
         f"{_PKG}/runtime/weight_board.py", "board slot size in MB"),
    Knob("DRL_SHM_WEIGHTS_ATTACH_S", "float", "5",
         f"{_PKG}/runtime/weight_board.py",
         "member-side bounded attach retry budget in seconds"),
    Knob("DRL_SHM_WEIGHTS_CREATE", "str", "unset",
         f"{_PKG}/runtime/transport.py",
         "learner side: board segment name to create and publish into"),
    Knob("DRL_SHM_WEIGHTS_NAME", "str", "unset",
         f"{_PKG}/runtime/transport.py",
         "actor/replica side: board segment name to attach"),
    Knob("DRL_CODEC_CACHE", "flag", "0",
         f"{_PKG}/data/codec.py",
         "schema-keyed encode header cache + layout-keyed decode cache"),
    Knob("DRL_OBS_DEDUP", "flag", "0",
         f"{_PKG}/data/codec.py",
         "frame-stack dedup on trajectory PUTs (base stack + new plane "
         "per step)"),
    Knob("DRL_PUT_BATCH", "int", "0 (off)", f"{_PKG}/data/fifo.py",
         "actor-side k-unroll batched PUT exchanges"),
    # -- weight plane -----------------------------------------------------
    Knob("DRL_WEIGHTS_SHARDED", "flag", "0",
         f"{_PKG}/runtime/weight_shards.py",
         "partition-keyed per-shard weight publication + shard-scoped "
         "TCP pulls"),
    Knob("DRL_WEIGHTS_QUANT", "enum(bf16|int8)", "off (`1` means bf16)",
         f"{_PKG}/runtime/weight_shards.py",
         "quantized actor-bound shard blobs (learner master copy stays "
         "f32)"),
    Knob("DRL_WEIGHTS_DELTA", "flag", "0",
         f"{_PKG}/runtime/weight_shards.py",
         "per-shard byte-range deltas between consecutive versions"),
    Knob("DRL_WEIGHTS_KEYS", "list", "unset (all shards)",
         f"{_PKG}/runtime/weight_shards.py",
         "scope a role's shard refreshes to the named keys"),
    Knob("DRL_PUBLISH_INTERVAL", "int", "unset → config section",
         f"{_PKG}/runtime/transport.py",
         "override the learner's weight publish cadence (steps)"),
    Knob("DRL_ASYNC_PUBLISH", "flag", "unset → on",
         f"{_PKG}/runtime/publishing.py",
         "weight D2H + store publish on a background worker"),
    Knob("DRL_ASYNC_METRICS", "flag", "unset → role default",
         f"{_PKG}/runtime/publishing.py",
         "metrics float()s absorbed off the learner thread"),
    # -- replay -----------------------------------------------------------
    Knob("DRL_REPLAY_SHARDS", "int", "2",
         f"{_PKG}/runtime/replay_shard.py",
         "N ingest-side prioritized replay shards (0 disables)"),
    Knob("DRL_REPLAY_SCORER", "enum(max|td_proxy)", "max",
         f"{_PKG}/runtime/replay_shard.py",
         "ingest-time initial-priority scorer"),
    Knob("DRL_REPLAY_SPILL", "flag", "1",
         f"{_PKG}/runtime/replay_shard.py",
         "tiered replay: each shard keeps a hot RAM set and spills cold "
         "segments to disk by priority mass; stored experience scales "
         "with disk while sampling stays exact"),
    Knob("DRL_REPLAY_SPILL_DIR", "str", "unset → checkpoint-dir sibling",
         f"{_PKG}/runtime/replay_shard.py",
         "spill directory (per-shard subdirs; restart recovery reads "
         "its manifest)"),
    Knob("DRL_REPLAY_SPILL_HOT_MB", "float", "256",
         f"{_PKG}/runtime/replay_shard.py",
         "per-shard hot-tier payload budget before segments spill"),
    Knob("DRL_REPLAY_SPILL_SEG", "int", "512",
         f"{_PKG}/runtime/replay_shard.py",
         "items per spill segment (the spill/promote IO grain)"),
    Knob("DRL_APEX_INGEST_UNROLLS", "int", "1",
         f"{_PKG}/runtime/apex_runner.py",
         "unrolls scored per TD forward + sum-tree batch add"),
    Knob("DRL_ACTOR_PRIORITY", "flag", "0",
         f"{_PKG}/data/admission.py",
         "sample-at-source: actors compute the DRL_REPLAY_SCORER "
         "priorities and stamp them on PUT blobs; the learner "
         "fast-accepts the stamp instead of re-scoring"),
    Knob("DRL_ADMISSION", "flag", "0",
         f"{_PKG}/data/admission.py",
         "priority-mass admission: under learner backpressure, thin "
         "low-priority unrolls at the actor (Horvitz-Thompson-corrected "
         "stamps; dropped mass folded into the next stamp)"),
    Knob("DRL_ADMISSION_LO", "float", "0.5",
         f"{_PKG}/data/admission.py",
         "learner pressure (0..1) at which admission engages"),
    Knob("DRL_ADMISSION_HI", "float", "0.9",
         f"{_PKG}/data/admission.py",
         "learner pressure of maximal thinning severity"),
    Knob("DRL_ADMISSION_FLOOR", "float", "0.1",
         f"{_PKG}/data/admission.py",
         "per-transition keep-probability floor under thinning"),
    Knob("DRL_ADMISSION_PRESSURE", "float", "unset → PUT-reply EWMA",
         f"{_PKG}/data/admission.py",
         "pressure override 0..1 (tests; beats both the TCP "
         "PUT-reply EWMA and the shm-ring header's pressure word)"),
    Knob("DRL_DEVICE_PATH", "flag", "0",
         f"{_PKG}/data/device_path.py",
         "fused device-resident sample path: background gather + "
         "double-buffered H2D + K-step scanned learn with one D2H per K"),
    Knob("DRL_DEVICE_PATH_DEPTH", "int", "1",
         f"{_PKG}/data/device_path.py",
         "device-resident sampled calls beyond the one in use (1 = "
         "classic double buffering)"),
    Knob("DRL_DEVICE_PATH_MAX_MB", "float", "256",
         f"{_PKG}/data/device_path.py",
         "stacked-call size past which the path demotes to the host "
         "loop"),
    Knob("DRL_R2D2_RECENT_FRACTION", "float", "0",
         f"{_PKG}/runtime/r2d2_runner.py",
         "fraction of each R2D2 batch drawn from the recent window"),
    Knob("DRL_R2D2_RECENT_WINDOW", "int", "8 × batch_size",
         f"{_PKG}/runtime/r2d2_runner.py",
         "recent-sequence window length (clamped to ring capacity)"),
    Knob("DRL_CKPT_REPLAY", "flag", "1", f"{_PKG}/utils/checkpoint.py",
         "include the replay snapshot in checkpoints"),
    Knob("DRL_CKPT_REPLAY_MAX_MB", "float", "512",
         f"{_PKG}/utils/checkpoint.py",
         "replay snapshot size cap per checkpoint"),
    # -- inference tier ---------------------------------------------------
    Knob("DRL_INFER_REPLICAS", "int", "0",
         f"{_PKG}/runtime/serving.py",
         "inference replica count for --remote_act (0 = learner-hosted)"),
    Knob("DRL_INFER_ADDRS", "list", "unset", f"{_PKG}/runtime/transport.py",
         "actor side: replica endpoints for RemoteActService"),
    Knob("DRL_INFER_PORT", "int", "server_port + 1000 + task",
         f"{_PKG}/runtime/serving.py", "per-replica serve port"),
    Knob("DRL_INFER_MAX_BATCH", "int", "256", f"{_PKG}/runtime/serving.py",
         "act batch bucket ceiling"),
    Knob("DRL_INFER_BUDGET", "int", "4 × max_batch",
         f"{_PKG}/runtime/serving.py",
         "admission-control pending-rows budget (over → ST_BUSY)"),
    Knob("DRL_INFER_DEPTH", "int", "2", f"{_PKG}/runtime/serving.py",
         "continuous batcher dispatched-but-unmaterialized depth"),
    Knob("DRL_INFER_PULL_S", "float", "0.2", f"{_PKG}/runtime/serving.py",
         "replica weight refresh cadence in seconds"),
    # -- fleet control plane ----------------------------------------------
    Knob("DRL_FLEET", "flag", "unset → on", f"{_PKG}/runtime/fleet.py",
         "fleet supervisor + heartbeats + re-promote ladders (0 restores "
         "one-way demotes)"),
    Knob("DRL_FLEET_HB_S", "float", "2.0", f"{_PKG}/runtime/fleet.py",
         "member heartbeat cadence in seconds"),
    Knob("DRL_FLEET_SUSPECT_S", "float", "3 × heartbeat",
         f"{_PKG}/runtime/fleet.py", "missed-beat window before SUSPECT"),
    Knob("DRL_FLEET_DEAD_S", "float", "10 × heartbeat",
         f"{_PKG}/runtime/fleet.py", "missed-beat window before DEAD"),
    Knob("DRL_REATTACH_BASE_S", "float", "2.0", f"{_PKG}/runtime/fleet.py",
         "re-promote ladder backoff base"),
    Knob("DRL_REATTACH_MAX_S", "float", "30.0", f"{_PKG}/runtime/fleet.py",
         "re-promote ladder backoff cap"),
    Knob("DRL_REATTACH_ATTEMPTS", "int", "8", f"{_PKG}/runtime/fleet.py",
         "probes per outage before the demotion latches permanent"),
    # -- actor data plane -------------------------------------------------
    Knob("DRL_ACTOR_PIPE", "flag", "0",
         f"{_PKG}/runtime/actor_pipeline.py",
         "double-buffered env slices + async unroll publisher"),
    Knob("DRL_ACTOR_PUB_DEPTH", "int", "2",
         f"{_PKG}/runtime/actor_pipeline.py",
         "publisher backpressure: max unpublished rounds before submit "
         "blocks"),
    # -- learner tier (runtime/learner_tier.py + parallel/collective.py) --
    Knob("DRL_LEARNER_SEATS", "int", "0 (no tier)",
         f"{_PKG}/runtime/learner_tier.py",
         "N cooperating learner seats (launcher gate; 0/1 = the single "
         "pre-tier learner)"),
    Knob("DRL_LEARNER_RANK", "int", "unset (no tier)",
         f"{_PKG}/runtime/learner_tier.py",
         "this learner process's seat rank (exported by the launcher)"),
    Knob("DRL_LEARNER_PEERS", "list", "unset (no tier)",
         f"{_PKG}/runtime/learner_tier.py",
         "collective endpoint roster host:port per rank (exported by "
         "the launcher)"),
    Knob("DRL_LEARNER_SYNC", "enum(allreduce|async)", "allreduce",
         f"{_PKG}/runtime/learner_tier.py",
         "seat gradient exchange: lockstep ring allreduce, or "
         "IMPACT-style bounded-staleness parameter merging"),
    Knob("DRL_LEARNER_WAIT_S", "float", "10",
         f"{_PKG}/parallel/collective.py",
         "bounded wait per collective exchange before probing the peer "
         "(unreachable past it = dead, membership re-forms)"),
    Knob("DRL_LEARNER_MERGE_STEPS", "int", "8",
         f"{_PKG}/runtime/learner_tier.py",
         "async mode: train steps between parameter merges"),
    Knob("DRL_LEARNER_STALE_MAX", "int", "4",
         f"{_PKG}/runtime/learner_tier.py",
         "async mode: drop peer contributions staler than this many "
         "merge rounds"),
    Knob("DRL_COLL_PARTITION", "flag", "1",
         f"{_PKG}/runtime/learner_tier.py",
         "partition-aware allreduce: classify gradient leaves by "
         "partition spec, replicated rides the ring, sharded classes "
         "go owner-scoped; 0 restores the plan-less full-vector ring "
         "byte-for-byte"),
    Knob("DRL_COLL_QUANT", "enum(f32|bf16)", "f32 (`1` means bf16)",
         f"{_PKG}/runtime/learner_tier.py",
         "gradient wire encoding for partitioned rounds: bf16 halves "
         "the bytes through the shared RNE codec (f32 master "
         "accumulation); folded into the plan hash — skewed seats "
         "refuse loudly"),
    Knob("DRL_COLL_OVERLAP", "int", "0",
         f"{_PKG}/runtime/learner_tier.py",
         "in-flight exchange depth (capped at 1): 1 overlaps round t's "
         "wire time with round t+1's backward via the tier's collective "
         "worker (delayed apply, one-step-stale pipelined SGD)"),
    # -- topology addressing ----------------------------------------------
    Knob("DRL_LEARNER_ADDR", "str", "unset → config server_ip:port",
         f"{_PKG}/runtime/transport.py",
         "non-learner roles: full learner address host:port"),
    Knob("DRL_LEARNER_INDEX", "int", "0", f"{_PKG}/runtime/transport.py",
         "non-learner roles: learner port offset (co-hosted learners)"),
    Knob("DRL_COORDINATOR", "str", "unset (single-host)",
         f"{_PKG}/parallel/distributed.py",
         "jax.distributed coordinator address"),
    Knob("DRL_NUM_PROCESSES", "int", "unset (single-host)",
         f"{_PKG}/parallel/distributed.py", "multi-host process count"),
    Knob("DRL_PROCESS_ID", "int", "unset (single-host)",
         f"{_PKG}/parallel/distributed.py", "this process's multi-host index"),
    # -- kernels ----------------------------------------------------------
    Knob("DRL_TPU_PALLAS", "flag", "1", f"{_PKG}/ops/pallas/__init__.py",
         "master gate for Pallas TPU kernels (0 forces reference ops)"),
    Knob("DRL_LSTM_PALLAS", "flag", "0 (opt-in)", f"{_PKG}/ops/lstm.py",
         "fused VMEM-resident Pallas LSTM recursion"),
    # -- envs -------------------------------------------------------------
    Knob("DRL_NO_GYMNASIUM", "flag", "0", f"{_PKG}/envs/registry.py",
         "ignore an installed gymnasium (force the synthetic sims)"),
    Knob("DRL_SYNTHETIC_ATARI", "flag", "0", f"{_PKG}/envs/registry.py",
         "acknowledge the synthetic-ALE fallback (silences the warning)"),
    # -- observability ----------------------------------------------------
    Knob("DRL_TELEMETRY", "flag", "0", f"{_PKG}/observability/metrics.py",
         "derive the telemetry shard dir from the process's --run_dir"),
    Knob("DRL_TELEMETRY_DIR", "str", "unset (telemetry off)",
         f"{_PKG}/observability/metrics.py", "explicit telemetry shard dir"),
    Knob("DRL_TELEMETRY_FLUSH_S", "float", "1.0",
         f"{_PKG}/observability/metrics.py", "telemetry flush cadence"),
    Knob("DRL_TRACE_MAX_EVENTS", "int", "100000",
         f"{_PKG}/observability/trace.py",
         "span cap per trace file (past it spans count as dropped)"),
    Knob("DRL_TRANSPORT_STATS_S", "float", "0 (off)",
         f"{_PKG}/runtime/transport.py",
         "periodic one-line data-plane stats cadence"),
    Knob("DRL_PROFILE_DIR", "str", "unset (profiling off)",
         f"{_PKG}/utils/profiling.py", "jax profiler trace directory"),
    Knob("DRL_PROFILE_START", "int", "10", f"{_PKG}/utils/profiling.py",
         "train step that starts the profiled window"),
    Knob("DRL_PROFILE_STEPS", "int", "5", f"{_PKG}/utils/profiling.py",
         "profiled window length in train steps"),
    # -- runtime sanitizer (tools/drlint/rt) ------------------------------
    Knob("DRL_SANITIZE", "flag", "0", f"{_PKG}/__init__.py",
         "install the drlint-rt runtime concurrency sanitizer at package "
         "import (instrumented locks, GuardedBy + blocking checks)"),
    Knob("DRL_SANITIZE_OUT", "str", "unset (findings to stderr only)",
         "tools/drlint/rt/sanitizer.py",
         "JSONL artifact path for findings/edges/accesses (the "
         "--reconcile input); multi-process safe via O_APPEND lines"),
    Knob("DRL_SANITIZE_HOLD_MS", "float", "1000",
         "tools/drlint/rt/sanitizer.py",
         "hold-time watchdog threshold: a lock held at least this long "
         "is an rt-hold finding"),
    Knob("DRL_SANITIZE_SCOPE", "list", "unset (repo tree only)",
         "tools/drlint/rt/sanitizer.py",
         "extra directories whose lock constructions/accesses are "
         "in-scope (the planted-fixture tests' tmp dirs)"),
    Knob("DRL_SANITIZE_MODEL", "str", "unset",
         "tools/drlint/rt/sanitizer.py",
         "static lock-order edge JSON; an observed edge contradicting "
         "it is an rt-lock-order finding even before a cycle closes"),
    Knob("DRL_SANITIZE_CENSUS", "flag", "unset → on",
         "tools/drlint/rt/census.py",
         "leak census under the sanitizer: thread/shm/socket factory "
         "registries + at-exit report of threads alive past owner "
         "close, segments never unlinked by their creator, attach-side "
         "unlinks, and unclosed sockets (0 disables)"),
    # -- test-surface gates (read outside the package) --------------------
    Knob("DRL_RUN_NATIVE_TSAN", "flag", "0 (env-documented skip)",
         "tests/test_native.py",
         "force the C++ tsan stress build/run despite the container's "
         "known-failing toolchain"),
    Knob("DRL_RUN_IMPALA_STALE", "flag", "0 (env-documented skip)",
         "tests/test_e2e.py",
         "force the FP-trajectory-sensitive publish-interval return test"),
    Knob("DRL_RUN_SANITIZE_MESH", "flag", "0 (env-documented skip)",
         "tests/test_learner_tier.py",
         "force the two-thread pjit-mesh tier test under DRL_SANITIZE=1 "
         "(wedges inside jax internals under the patched lock factories)"),
    Knob("DRL_TPU_SLOW_TESTS", "flag", "0", "tests/test_models.py",
         "opt into the slow conv-torso test tier (minutes of XLA:CPU "
         "compiles)"),
]

KNOBS: dict[str, Knob] = {}
for _k in _ENTRIES:
    if _k.name in KNOBS:
        raise ValueError(f"duplicate knob registration: {_k.name}")
    KNOBS[_k.name] = _k
del _k


# -- docs table ---------------------------------------------------------------


def generate_table() -> str:
    """The markdown knob table, sorted by name — byte-for-byte what
    docs/performance.md must contain between the registry markers."""
    lines = ["| Knob | Type | Default | Owner | Meaning |",
             "|---|---|---|---|---|"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        lines.append(f"| `{k.name}` | {k.type} | {k.default} | "
                     f"`{k.owner}` | {k.doc} |")
    return "\n".join(lines)


def render_block() -> str:
    return f"{BEGIN_MARK}\n{generate_table()}\n{END_MARK}"


def extract_block(docs_text: str) -> str | None:
    """The registry block currently in a docs file, or None."""
    try:
        start = docs_text.index(BEGIN_MARK)
        end = docs_text.index(END_MARK) + len(END_MARK)
    except ValueError:
        return None
    return docs_text[start:end]


def docs_drift(docs_text: str) -> str | None:
    """None when the docs carry exactly the generated table; else a
    human message naming the first divergence."""
    block = extract_block(docs_text)
    if block is None:
        return ("knob table markers missing — run `python -m "
                "tools.drlint.knobs --write`")
    want = render_block()
    if block == want:
        return None
    have_lines = block.splitlines()
    want_lines = want.splitlines()
    for i, (h, w) in enumerate(zip(have_lines, want_lines)):
        if h != w:
            return (f"knob table drifted from the registry at block line "
                    f"{i + 1}: have {h!r}, registry says {w!r}")
    return (f"knob table drifted from the registry: {len(have_lines)} "
            f"lines in docs vs {len(want_lines)} generated")


def write_docs(path: str = DOCS_PATH) -> bool:
    """Regenerate the registry block in place; True if the file changed.
    The block must already exist (markers placed once, by hand)."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    block = extract_block(text)
    if block is None:
        raise SystemExit(f"{path}: no knob-table markers; add\n"
                         f"{BEGIN_MARK}\n{END_MARK}\nwhere the table belongs")
    new = text.replace(block, render_block())
    if new == text:
        return False
    with open(path, "w", encoding="utf-8") as f:
        f.write(new)
    return True


# -- tree round-trip ----------------------------------------------------------


def knob_nodes(tree: ast.AST) -> list[tuple[str, ast.Constant]]:
    """(name, node) for every DRL_* string literal in a parsed module —
    THE single definition of what counts as a knob reference, shared by
    the `knob-registry` lint pass and the tree round-trip. Reads, env
    exports to child processes, and test monkeypatches all count: each
    is a coupling to the knob's contract."""
    return [(node.value, node) for node in ast.walk(tree)
            if isinstance(node, ast.Constant) and isinstance(node.value, str)
            and KNOB_RE.match(node.value)]


def knob_literals(src: str) -> list[tuple[str, int]]:
    """(name, line) view of `knob_nodes` over one source blob."""
    return [(name, node.lineno) for name, node in knob_nodes(ast.parse(src))]


def scan_tree(paths: list[str] | None = None) -> dict[str, list[tuple[str, int]]]:
    """name -> [(repo-relative path, line)] across the given trees
    (default: the package, scripts/, tests/ and tools/)."""
    if paths is None:
        paths = [os.path.join(_REPO_ROOT, p)
                 for p in (_PKG, "scripts", "tests", "tools")]
    from tools.drlint.core import iter_py_files

    out: dict[str, list[tuple[str, int]]] = {}
    for root in paths:
        # One tree-walk implementation for the whole linter: the same
        # hidden-dir/__pycache__ pruning the lint gate uses, so the
        # round-trip and the gate can never disagree on which files
        # exist.
        try:
            files = iter_py_files([root])
        except FileNotFoundError:
            continue
        for fp in files:
            rel = os.path.relpath(fp, _REPO_ROOT).replace(os.sep, "/")
            if rel in SCAN_EXCLUDE:
                continue
            try:
                with open(fp, encoding="utf-8") as f:
                    src = f.read()
                hits = knob_literals(src)
            except (OSError, SyntaxError, UnicodeDecodeError):
                continue
            for name, line in hits:
                out.setdefault(name, []).append((rel, line))
    return out


def round_trip(paths: list[str] | None = None
               ) -> tuple[dict[str, list], list[str]]:
    """-> (unregistered reads, stale registry entries) over the tree."""
    reads = scan_tree(paths)
    unregistered = {name: sites for name, sites in reads.items()
                    if name not in KNOBS}
    stale = sorted(name for name in KNOBS
                   if name not in reads)
    return unregistered, stale


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tools.drlint.knobs",
        description="DRL_* knob registry: docs table generation + checks")
    ap.add_argument("--write", action="store_true",
                    help=f"regenerate the table in {DOCS_PATH}")
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) on docs drift or tree round-trip "
                         "failures")
    ap.add_argument("--docs", default=DOCS_PATH)
    args = ap.parse_args(argv)
    if args.write:
        changed = write_docs(args.docs)
        print(f"knobs: {args.docs} "
              f"{'updated' if changed else 'already current'}")
        return 0
    if args.check:
        rc = 0
        with open(args.docs, encoding="utf-8") as f:
            drift = docs_drift(f.read())
        if drift:
            print(f"knobs: {drift}")
            rc = 1
        unregistered, stale = round_trip()
        for name, sites in sorted(unregistered.items()):
            where = ", ".join(f"{p}:{ln}" for p, ln in sites[:3])
            print(f"knobs: unregistered knob {name} read at {where}")
            rc = 1
        for name in stale:
            print(f"knobs: stale registry entry {name} (never read in tree)")
            rc = 1
        if rc == 0:
            print(f"knobs: {len(KNOBS)} knobs registered, docs table "
                  f"current, tree round-trips")
        return rc
    print(generate_table())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
