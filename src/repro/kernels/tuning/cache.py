"""Deterministic on-disk JSON cache for tuned kernel configs.

One file (``tuning_cache.json`` under the cache directory) holds every
tuned entry, grouped by **backend fingerprint** — jax version + platform
+ device kind — so a cache written on one backend can never leak tile
choices onto another: a fingerprint change is a cold miss, not a wrong
answer.  Writes are deterministic (sorted keys, stable separators) so a
committed cache diffs cleanly.

Entry keys are flat strings::

    <kernel>|<kind>|<shape as AxBxC>|<dtype>|<plan>

where ``plan`` is ``default`` or the short digest of the routing-plan
compile key the Dispatcher was building under (see ``tuning.plan_scope``)
— the RedMulE-FT observation that a degraded plan can prefer different
tiling than the healthy one, made concrete in the key.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
from typing import Dict, Mapping, Optional, Sequence, Tuple

SCHEMA = 1
DEFAULT_PLAN = "default"


def backend_fingerprint() -> str:
    """jax version + platform + device kind: the cache partition key."""
    import jax

    dev = jax.devices()[0]
    return f"jax-{jax.__version__}/{dev.platform}/{dev.device_kind}"


def plan_digest(plan_key) -> str:
    """Short, process-stable digest of a Dispatcher plan key.

    RoutingPlan / FleetPlan.compile_key() are frozen tuples with
    deterministic reprs; the builtin ``hash`` is salted per process, so
    the digest hashes the repr instead.
    """
    if plan_key is None:
        return DEFAULT_PLAN
    return hashlib.sha256(repr(plan_key).encode()).hexdigest()[:12]


def entry_key(kernel: str, kind: str, shape: Sequence[int], dtype,
              plan: Optional[str] = None) -> str:
    shape_s = "x".join(str(int(d)) for d in shape)
    dtype_s = getattr(dtype, "name", None) or str(dtype)
    return f"{kernel}|{kind}|{shape_s}|{dtype_s}|{plan or DEFAULT_PLAN}"


def default_cache_dir() -> str:
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    # repo-root artifacts/tuning (three levels up from this file's package)
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(here))))
    return os.path.join(root, "artifacts", "tuning")


class TuningCache:
    """Load-once, write-atomically JSON cache of tuned configs.

    ``get`` returns the stored config dict (plus ``us`` measurement
    metadata under ``_meta``-prefixed keys stripped) or None; it never
    raises — a corrupt or unreadable cache behaves as empty, because a
    missing tuning entry must only ever cost performance, not correctness.
    """

    def __init__(self, path: Optional[str] = None,
                 fingerprint: Optional[str] = None):
        self.dir = path or default_cache_dir()
        self.path = os.path.join(self.dir, "tuning_cache.json")
        self.fingerprint = fingerprint or backend_fingerprint()
        self._lock = threading.Lock()
        self._doc: Optional[Dict] = None

    # ----------------------------------------------------------- loading
    def _load(self) -> Dict:
        if self._doc is None:
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                if not isinstance(doc, dict) or \
                        not isinstance(doc.get("by_backend"), dict):
                    raise ValueError("malformed tuning cache")
            except Exception:
                doc = {"schema": SCHEMA, "by_backend": {}}
            self._doc = doc
        return self._doc

    def invalidate(self) -> None:
        """Drop the in-memory copy (re-read on next access)."""
        with self._lock:
            self._doc = None

    # ------------------------------------------------------------ access
    def _section(self) -> Dict:
        return self._load()["by_backend"].setdefault(self.fingerprint, {})

    def get(self, kernel: str, kind: str, shape: Sequence[int], dtype,
            plan: Optional[str] = None) -> Optional[Dict[str, int]]:
        try:
            with self._lock:
                entry = self._section().get(
                    entry_key(kernel, kind, shape, dtype, plan))
            if not isinstance(entry, dict):
                return None
            return {k: v for k, v in entry.items()
                    if not k.startswith("_")}
        except Exception:
            return None

    def entries(self) -> Dict[str, Dict]:
        """This backend's full section (tests / bench stats)."""
        with self._lock:
            return dict(self._section())

    def put(self, kernel: str, kind: str, shape: Sequence[int], dtype,
            cfg: Mapping[str, int], *, plan: Optional[str] = None,
            us: Optional[float] = None, evals: Optional[int] = None,
            persist: bool = True) -> None:
        entry = {k: int(v) for k, v in sorted(cfg.items())}
        if us is not None:
            entry["_us"] = round(float(us), 3)
        if evals is not None:
            entry["_evals"] = int(evals)
        with self._lock:
            self._section()[entry_key(kernel, kind, shape, dtype, plan)] \
                = entry
            if persist:
                self._flush()

    # --------------------------------------------------------- persisting
    def _flush(self) -> None:
        doc = self._load()
        os.makedirs(self.dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, sort_keys=True, indent=1,
                          separators=(",", ": "))
                f.write("\n")
            os.replace(tmp, self.path)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


# Stats shared by every lookup path (surfaced in BENCH_*.json).
class TunerStats:
    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.tuned = 0

    def as_dict(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "tuned": self.tuned}

    def reset(self) -> None:
        self.hits = self.misses = self.tuned = 0


STATS = TunerStats()


def shape_key(kernel: str, args: Tuple) -> Tuple[int, ...]:
    """Canonical shape tuple for a kernel call (documented in space.py)."""
    if kernel == "flash_attention":
        q, k = args[0], args[1]
        B, Sq, H, D = q.shape
        return (B, Sq, k.shape[1], H, k.shape[2], D)
    if kernel == "swiglu_mlp":
        x, w1 = args[0], args[1]
        return (x.shape[0], x.shape[1], w1.shape[1])
    if kernel == "mamba2_ssd":
        x, B_ = args[0], args[3]
        return (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                B_.shape[-1])
    if kernel == "rwkv6_wkv":
        r, v = args[0], args[2]
        return (r.shape[0], r.shape[1], r.shape[2], r.shape[3],
                v.shape[-1])
    raise KeyError(f"no canonical shape for kernel {kernel!r}")
