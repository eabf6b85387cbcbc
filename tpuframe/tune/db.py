"""Persistent tuning database (JSON), consulted at startup by ``train.py``,
``bench.py`` and ``ops/flash_attention.py``.

Records are keyed by (program, topology, generation, config) and carry a
two-tier score:

  predicted — written by the offline AOT sweep (``tpuframe.tune.search``):
              roofline lower-bound ms, binding resource, fits verdict,
              VMEM footprint for pallas candidates.  Compiler-measured,
              never chip-measured.
  measured  — written when a chip window opens and
              ``obs.autotune.replay_offline_topk`` re-runs the offline
              top-k through the real measured loop, upgrading the record.

Resolution precedence (docs/DESIGN.md "The tuning subsystem"):

    env override  >  measured  >  predicted  >  hard default

(flash-attention blocks skip the predicted tier: the kernel's own shape
rule is their default, and only a row a chip ran outranks it), and DB
resolution only engages when the target TPU generation is named
explicitly (``TPUFRAME_TUNE_GEN``) — a plain CPU test run, and a plain
chip run, see the hard defaults, untouched.  The generation the device
itself reports (``tune.roofline.device_generation``) prices MFU rows and
picks kernel layouts; it never switches the DB on.

Pure stdlib; import-time cost is nil by design (flash_attention resolves
its block sizes through here at import).
"""

from __future__ import annotations

import hashlib
import json
import os

SCHEMA_VERSION = 1

# Env knobs.  TPUFRAME_TUNE_DB: path to the DB file; "", "0" or "off"
# disables DB resolution entirely.  TPUFRAME_TUNE_GEN: target generation
# for resolution — the one switch that engages the DB.
_DB_ENV = "TPUFRAME_TUNE_DB"
_GEN_ENV = "TPUFRAME_TUNE_GEN"
_OFF = ("", "0", "off", "none")

_REQUIRED_KEYS = ("program", "family", "fingerprint", "topology",
                  "generation", "config", "predicted")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_db_path() -> str:
    env = os.environ.get(_DB_ENV)
    if env and env.strip().lower() not in _OFF:
        return env
    return os.path.join(repo_root(), "tune_db.json")


def db_disabled() -> bool:
    env = os.environ.get(_DB_ENV)
    return env is not None and env.strip().lower() in _OFF


def target_generation() -> str | None:
    """The TPU generation runtime resolution should tune for, or None when
    not named (-> callers keep their hard defaults)."""
    val = os.environ.get(_GEN_ENV, "").strip().lower()
    return val.split(":", 1)[0] if val else None


def fingerprint(desc, xla_opts: dict | None = None) -> str:
    """Stable program fingerprint: sha256 over the canonical JSON of a
    program description plus the (sorted) compiler-option set — so a seeded
    ``TPUFRAME_XLA_OPTS`` candidate yields a different fingerprint even
    when the lowered module text is identical (compiler options travel in
    the compile request, not the module)."""
    payload = {"desc": desc,
               "xla_opts": sorted((xla_opts or {}).items())}
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


class Record:
    """Thin read-mostly wrapper over one DB record dict."""

    def __init__(self, data: dict):
        self.data = data

    def __getitem__(self, k):
        return self.data[k]

    def get(self, k, default=None):
        return self.data.get(k, default)

    @property
    def program(self) -> str:
        return self.data["program"]

    @property
    def family(self) -> str:
        return self.data["family"]

    @property
    def generation(self) -> str:
        return self.data["generation"]

    @property
    def topology(self) -> str:
        return self.data["topology"]

    @property
    def config(self) -> dict:
        return self.data.get("config", {})

    @property
    def predicted(self) -> dict:
        return self.data.get("predicted", {})

    @property
    def measured(self) -> dict | None:
        return self.data.get("measured")

    def env_overrides(self) -> dict:
        """This record's config as the env vars the existing knobs read —
        the bridge into ``obs.autotune``'s subprocess measure loop."""
        env = {}
        cfg = self.config
        if "fa_block_q" in cfg:
            env["TPUFRAME_FA_BLOCK_Q"] = str(cfg["fa_block_q"])
        if "fa_block_k" in cfg:
            env["TPUFRAME_FA_BLOCK_K"] = str(cfg["fa_block_k"])
        if cfg.get("xla_opts"):
            env["TPUFRAME_XLA_OPTS"] = ",".join(
                f"{k}={v}" for k, v in sorted(cfg["xla_opts"].items()))
        if "batch" in cfg:
            env["TPUFRAME_BENCH_BATCH"] = str(cfg["batch"])
        if "remat_policy" in cfg:
            env["TPUFRAME_REMAT_POLICY"] = str(cfg["remat_policy"])
        if "weight_update" in cfg:
            env["TPUFRAME_WEIGHT_UPDATE"] = str(cfg["weight_update"])
        if "hier" in cfg:
            env["TPUFRAME_HIER"] = str(cfg["hier"])
        if "fusion_threshold" in cfg:
            env["TPUFRAME_FUSION_THRESHOLD"] = str(cfg["fusion_threshold"])
        if "spec" in cfg:
            env["TPUFRAME_SPEC"] = str(cfg["spec"])
        if "decode_block" in cfg:
            env["TPUFRAME_DECODE_BLOCK"] = str(cfg["decode_block"])
        if cfg.get("prompt_buckets"):
            env["TPUFRAME_SERVE_BUCKETS"] = ",".join(
                str(b) for b in cfg["prompt_buckets"])
        return env

    def _key(self):
        return (self.program, self.topology, self.generation,
                json.dumps(self.config, sort_keys=True))

    def _rank(self):
        """Sort key, best first.  Measured tier always beats predicted.
        Within measured: higher value wins when the measure maximizes
        (throughput — obs.autotune's convention), else lower.  Within
        predicted: lower roofline ms, then higher VMEM utilization — for
        pallas kernels cost_analysis cannot see inside the custom call
        (PERF.md §8), so roofline ms ties across block sizes and the
        fatter in-budget tiling (fewer grid steps, better pipelining) is
        the honest tiebreak."""
        m = self.measured
        if m and m.get("value") is not None:
            v = float(m["value"])
            return (0, -v if m.get("maximize", True) else v)
        p = self.predicted
        ms = p.get("predicted_ms")
        ms = float("inf") if ms is None else float(ms)
        return (1, ms, -float(p.get("vmem_bytes") or 0))


class TuningDB:
    def __init__(self, path: str, data: dict | None = None):
        self.path = path
        self.data = data or {"version": SCHEMA_VERSION, "records": []}

    @classmethod
    def open(cls, path: str | None = None) -> "TuningDB":
        path = path or default_db_path()
        data = None
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        db = cls(path, data)
        problems = validate(db.data)
        if problems:
            raise ValueError(f"tuning DB {path}: " + "; ".join(problems))
        return db

    def save(self, path: str | None = None) -> str:
        path = path or self.path
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.data, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    def records(self, *, program: str | None = None,
                family: str | None = None,
                generation: str | None = None,
                topology: str | None = None) -> list:
        out = []
        for raw in self.data.get("records", []):
            rec = Record(raw)
            if program is not None and rec.program != program:
                continue
            if family is not None and rec.family != family:
                continue
            if generation is not None and rec.generation != generation:
                continue
            if topology is not None and rec.topology != topology:
                continue
            out.append(rec)
        return out

    def add(self, record: dict) -> Record:
        """Insert or replace (same program/topology/generation/config key
        replaces — a re-sweep supersedes its own older predictions but
        never clobbers a different config's measured entry)."""
        missing = [k for k in _REQUIRED_KEYS if k not in record]
        if missing:
            raise ValueError(f"tuning record missing keys {missing}")
        rec = Record(record)
        kept = [r for r in self.data["records"]
                if Record(r)._key() != rec._key()]
        kept.append(record)
        self.data["records"] = kept
        return rec

    def top_k(self, k: int = 3, **filters) -> list:
        return sorted(self.records(**filters),
                      key=lambda r: r._rank())[:k]

    def best(self, **filters) -> Record | None:
        top = self.top_k(1, **filters)
        return top[0] if top else None

    def upgrade_measured(self, record: Record, value: float, *,
                         unit: str = "value", maximize: bool = True,
                         at: str | None = None) -> None:
        """Predicted -> measured upgrade in place (call save() after)."""
        for raw in self.data["records"]:
            if Record(raw)._key() == record._key():
                raw["measured"] = {"value": value, "unit": unit,
                                   "maximize": maximize}
                if at is not None:
                    raw["measured"]["at"] = at
                return
        raise KeyError(f"record not in DB: {record.program} "
                       f"{record.config}")

    def lookup(self, program: str, fp: str, **filters) -> Record | None:
        """Fingerprint-checked lookup: best record for ``program`` whose
        fingerprint matches ``fp``.  A mismatch (the program changed since
        the sweep) returns None — callers fall back to defaults rather
        than apply a stale tuning."""
        for rec in self.top_k(k=10 ** 6, program=program, **filters):
            if rec["fingerprint"] == fp:
                return rec
        return None


def validate(data) -> list:
    """Schema validation for the CI gate.  Returns problem strings."""
    problems = []
    if not isinstance(data, dict):
        return [f"DB root must be an object, got {type(data).__name__}"]
    if data.get("version") != SCHEMA_VERSION:
        problems.append(f"version {data.get('version')!r} != "
                        f"{SCHEMA_VERSION}")
    recs = data.get("records")
    if not isinstance(recs, list):
        return problems + ["'records' must be a list"]
    for i, raw in enumerate(recs):
        if not isinstance(raw, dict):
            problems.append(f"records[{i}]: not an object")
            continue
        missing = [k for k in _REQUIRED_KEYS if k not in raw]
        if missing:
            problems.append(f"records[{i}]: missing {missing}")
            continue
        if not isinstance(raw["config"], dict):
            problems.append(f"records[{i}]: config must be an object")
        pred = raw["predicted"]
        if not isinstance(pred, dict):
            problems.append(f"records[{i}]: predicted must be an object")
        m = raw.get("measured")
        if m is not None and (not isinstance(m, dict) or "value" not in m):
            problems.append(f"records[{i}]: measured needs a 'value'")
        gen = str(raw["generation"])
        from tpuframe.tune import roofline
        if gen.split(":", 1)[0] not in roofline.HARDWARE:
            problems.append(f"records[{i}]: unknown generation {gen!r}")
    return problems


def _open_for_resolution() -> TuningDB | None:
    if db_disabled():
        return None
    path = default_db_path()
    if not os.path.exists(path):
        return None
    try:
        return TuningDB.open(path)
    except Exception:  # noqa: BLE001 — a corrupt DB must never take down
        return None    # a training run; the analysis gate reports it.


def resolve_fa_blocks(default_q, default_k) -> tuple:
    """Flash-attention block sizes: env > measured > default.  The default
    is the caller's (``None`` in ``ops/flash_attention.py``: its shape rule
    decides); a row that was only ever predicted never outranks it.  The
    DB tier only engages when the target generation is known — plain CPU
    runs (the whole fast test tier) see the defaults."""
    q, k = default_q, default_k
    gen = target_generation()
    if gen is not None:
        db = _open_for_resolution()
        if db is not None:
            rec = db.best(family="flash_attention", generation=gen)
            if rec is not None and rec.measured:
                q = int(rec.config.get("fa_block_q", q))
                k = int(rec.config.get("fa_block_k", k))
    env_q = os.environ.get("TPUFRAME_FA_BLOCK_Q")
    env_k = os.environ.get("TPUFRAME_FA_BLOCK_K")
    if env_q:
        q = int(env_q)
    if env_k:
        k = int(env_k)
    return q, k


def resolve_xla_opts(program: str, family: str | None = None) -> dict | None:
    """Compiler-option set for ``program``: None unless the DB has a tuned
    set for the target generation.  Callers apply ``TPUFRAME_XLA_OPTS``
    themselves FIRST (via utils.xla_opts.from_env) — when that env var is
    set this returns None so the override is unambiguous."""
    if os.environ.get("TPUFRAME_XLA_OPTS", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, generation=gen)
    if rec is None and family is not None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    opts = rec.config.get("xla_opts")
    return dict(opts) if opts else None


def resolve_remat_policy(program: str,
                         family: str | None = None) -> str | None:
    """Rematerialization policy for ``program``: None unless the DB has a
    swept winner for the target generation.  Callers apply
    ``TPUFRAME_REMAT_POLICY`` (and the legacy ``TPUFRAME_BENCH_REMAT``
    alias) themselves FIRST via :func:`tpuframe.mem.policy_from_env` —
    when either env var is set this returns None so the override is
    unambiguous."""
    if os.environ.get("TPUFRAME_REMAT_POLICY", "").strip():
        return None
    if os.environ.get("TPUFRAME_BENCH_REMAT", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, generation=gen)
    if (rec is None or "remat_policy" not in rec.config) \
            and family is not None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    pol = rec.config.get("remat_policy")
    return str(pol) if pol else None


def resolve_weight_update(program: str,
                          family: str | None = None) -> str | None:
    """Weight-update sharding mode for ``program``: None unless the DB has
    a swept ``weight_update_*`` winner for the target generation.  Callers
    apply ``TPUFRAME_WEIGHT_UPDATE`` themselves FIRST via
    :func:`tpuframe.parallel.zero1.resolve` — when the env var is set this
    returns None so the override is unambiguous."""
    if os.environ.get("TPUFRAME_WEIGHT_UPDATE", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, generation=gen)
    if (rec is None or "weight_update" not in rec.config) \
            and family is not None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    mode = rec.config.get("weight_update")
    return str(mode) if mode else None


def resolve_hier(program: str,
                 family: str | None = None) -> str | None:
    """Hierarchical-collective mode (flat/hier) for ``program``: None
    unless the DB has a swept ``hier_collectives`` winner for the target
    generation.  Callers apply ``TPUFRAME_HIER`` themselves FIRST via
    :func:`tpuframe.parallel.hier.resolve` — when the env var is set
    this returns None so the override is unambiguous."""
    if os.environ.get("TPUFRAME_HIER", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, generation=gen)
    if (rec is None or "hier" not in rec.config) and family is not None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    mode = rec.config.get("hier")
    return str(mode) if mode else None


def resolve_fusion_threshold(program: str,
                             family: str | None = None) -> int | None:
    """Gradient-fusion bucket threshold (bytes) for ``program``: None
    unless the DB has a swept ``fusion_threshold`` winner for the target
    generation.  Callers apply ``TPUFRAME_FUSION_THRESHOLD`` themselves
    FIRST via :func:`tpuframe.parallel.fusion.resolve` — when the env
    var is set this returns None so the override is unambiguous."""
    if os.environ.get("TPUFRAME_FUSION_THRESHOLD", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, generation=gen)
    if (rec is None or "fusion_threshold" not in rec.config) \
            and family is not None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    threshold = rec.config.get("fusion_threshold")
    try:
        return int(threshold) if threshold is not None else None
    except (TypeError, ValueError):
        return None


def resolve_spec(program: str,
                 family: str = "plan_spec") -> str | None:
    """Planned parallelism spec for ``program``: None unless the DB has a
    ``tune plan`` winner for the target generation.  Callers apply
    ``TPUFRAME_SPEC`` themselves FIRST via
    :func:`tpuframe.parallel.pspec.resolve` — when the env var is set (or
    an explicit spec argument was given) this returns None so the
    override is unambiguous.  Returns the canonical spec string the
    planner persisted (``config["spec"]``)."""
    if os.environ.get("TPUFRAME_SPEC", "").strip():
        return None
    gen = target_generation()
    if gen is None:
        return None
    db = _open_for_resolution()
    if db is None:
        return None
    rec = db.best(program=program, family=family, generation=gen)
    if rec is None:
        rec = db.best(family=family, generation=gen)
    if rec is None:
        return None
    spec = rec.config.get("spec")
    return str(spec) if spec else None


def resolve_decode_block(default: int = 128) -> int:
    """Serving KV-capacity granularity: env (``TPUFRAME_DECODE_BLOCK``)
    > tune-DB ``serve_lm`` winner > default.  Same generation gate as
    every other knob — plain CPU runs see the hard default."""
    block = default
    gen = target_generation()
    if gen is not None:
        db = _open_for_resolution()
        if db is not None:
            rec = db.best(family="serve_lm", generation=gen)
            if rec is not None and "decode_block" in rec.config:
                block = int(rec.config["decode_block"])
    env = os.environ.get("TPUFRAME_DECODE_BLOCK")
    if env and env.strip():
        block = int(env)
    return block


def resolve_serve_buckets(default: tuple) -> tuple:
    """Serving prompt-length buckets: env (``TPUFRAME_SERVE_BUCKETS``,
    comma-separated) > tune-DB ``serve_lm`` winner > default."""
    buckets = tuple(default)
    gen = target_generation()
    if gen is not None:
        db = _open_for_resolution()
        if db is not None:
            rec = db.best(family="serve_lm", generation=gen)
            if rec is not None and rec.config.get("prompt_buckets"):
                buckets = tuple(int(b)
                                for b in rec.config["prompt_buckets"])
    env = os.environ.get("TPUFRAME_SERVE_BUCKETS")
    if env and env.strip():
        from tpuframe.serve.kv_cache import parse_buckets
        buckets = parse_buckets(env)
    return buckets
