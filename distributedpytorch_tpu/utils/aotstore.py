"""Content-addressed on-disk store of serialized AOT serve executables.

Every serve worker pays (buckets x replicas) XLA compiles at startup —
minutes of redundant work on TPU for programs that are byte-identical
across incarnations of the same engine (fleet cold start, elastic
relaunch, repeated bench_serve legs). This store persists each compiled
bucket executable once (``jax.experimental.serialize_executable``) and
loads it on every later cold start, turning startup from compile-bound
into load-bound.

**Keying.** An entry's key is a hash of everything that changes the
compiled program: the PR-13 ``engine_fingerprint`` (model arch /
resolution / widths / s2d / quantization / kernels — obs/reqtrace.py),
the bucket's concrete input shape + dtype, the resolved kernel policy
and on-device mask threshold, and the device the executable is pinned
to (serve executables carry a ``SingleDeviceSharding``; deserializing
restores that device assignment, so replica N's entry is only correct
for device N).

**Skew and corruption.** The runtime that compiled an entry (jax /
jaxlib versions, backend platform) is recorded in the entry header and
cross-checked at load — NOT folded into the key — so a version bump
refuses the stale entry *loudly* (``result="skew"``, a logged note,
counter + flight-ring event) and falls back to compile-and-persist.
This is the same loud-refusal idiom as the profile/priors loaders
(obs/reqtrace.load_profile, ops/kernels.load_priors): a corrupt or
skewed entry is a miss-with-note, never a crash, never a silent
wrong-program load.

**Torn writes.** Entries are written with the checkpoint.py writer
idiom: unique tmp name, sha256 integrity footer, atomic
``os.replace`` — a worker SIGKILLed mid-persist leaves at most a stale
``*.tmp.*`` file, never a torn entry that poisons the next cold start.
Co-launched ranks racing the same key both rename complete
same-content files, so one shared store dir serves a whole fleet
(unlike the per-rank XLA compilation-cache split in dist/elastic.py).

CLI: ``python -m distributedpytorch_tpu aot {warm,ls,gc}`` — prewarm a
bucket ladder from a checkpoint, inspect entries, bound disk with LRU
eviction. Store dir resolution everywhere: explicit ``--aot-cache`` /
engine arg wins, else ``$DPT_AOT_CACHE``, else the store is off.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import json
import logging
import os
import pickle
import time
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

ENV_VAR = "DPT_AOT_CACHE"
KEY_SCHEME_ENV = "DPT_AOT_KEY_SCHEME"
ENTRY_KIND = "dpt_aot_executable"
ENTRY_VERSION = 1
ENTRY_SUFFIX = ".aotx"

_HASH_MAGIC = b"#DPT_AOT_SHA256:"
_FOOTER_LEN = len(_HASH_MAGIC) + 32
# unique tmp names: two replicas of one engine persisting different
# buckets concurrently must not clobber each other's tmp files
_TMP_COUNTER = itertools.count()

#: Runtime fields recorded in every entry header and cross-checked at
#: load. Deliberately NOT part of the key: a jaxlib upgrade must read
#: as a loud "skew" refusal on the existing entries, not a silent
#: cache reset.
RUNTIME_FIELDS = ("jax", "jaxlib", "backend")


class AOTEntryError(Exception):
    """One unusable store entry (torn, corrupt, or schema-broken) —
    always caught inside :meth:`AOTStore.load` and converted to a
    counted ``skew`` refusal."""


@contextlib.contextmanager
def no_xla_compilation_cache():
    """A window in which jax's persistent compilation cache is REALLY
    off — for both reads and writes.

    The AOT store replaces exactly what the XLA cache would provide, and
    the two must never compose: an executable rehydrated from the XLA
    cache serializes WITHOUT its backend kernel symbols, so a store
    entry written from (or a load routed through) a cache hit dies on
    the next deserialize with "Symbols not found". Flipping
    ``jax_enable_compilation_cache`` alone is NOT enough: jax memoizes
    "is the cache used" process-wide at the first compile
    (``compilation_cache.is_cache_used``), after which per-call flag
    flips are ignored. So the window resets that memoized state on the
    way in (re-checked lazily against the now-disabled flag) and again
    on the way out (so later ordinary compiles re-enable the cache).
    Disk contents are untouched either way.
    """
    import jax

    try:
        from jax._src import compilation_cache as _cc
        reset = _cc.reset_cache
    except Exception:  # pragma: no cover — future-jax fallback
        reset = lambda: None  # noqa: E731
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        reset()


def runtime_versions() -> Dict[str, str]:
    """The compiling/loading runtime's identity — a seam (tests fake a
    jaxlib bump by monkeypatching this module attribute)."""
    import jax
    import jaxlib

    return {
        "jax": str(jax.__version__),
        "jaxlib": str(jaxlib.__version__),
        "backend": str(jax.default_backend()),
    }


def device_key(device) -> str:
    """The key's device component for one replica device.

    Default (``exact``) scheme pins ``str(device)`` — the platform's
    full decoration, e.g. ``TPU_0(process=0,(0,0,0,0))`` — which is
    always correct but means identical chips in different processes of
    a pod slice (different coords in the decoration) never share
    entries. ``DPT_AOT_KEY_SCHEME=kind`` relaxes the component to
    ``platform:device_kind:ordinal``: same-kind chips at the same local
    ordinal produce the SAME key across hosts/processes/incarnations,
    so a shared store dir serves a whole fleet and a scaled-up replica
    group re-loads the entries any sibling (or a previous incarnation,
    or ``aot warm``) already persisted.

    The local ordinal stays IN the key under both schemes: a
    deserialized executable is pinned to its compile-time device and
    refuses inputs placed anywhere else, so ordinal N's entry is only
    correct for ordinal N. Skew-refusal semantics are unchanged — the
    scheme string lands in ``meta["device"]``, is recorded in the entry
    header, and is re-verified at load like every other meta field."""
    scheme = (os.environ.get(KEY_SCHEME_ENV) or "exact").strip().lower()
    if scheme == "kind":
        platform = getattr(device, "platform", "") or ""
        kind = getattr(device, "device_kind", "") or platform
        ordinal = getattr(device, "id", 0)
        return f"{platform}:{kind}:{int(ordinal)}"
    if scheme not in ("", "exact"):
        logger.warning(
            "unknown $%s=%r — falling back to the exact device-string "
            "scheme", KEY_SCHEME_ENV, scheme,
        )
    return str(device)


def entry_key(
    fingerprint: str,
    bucket: int,
    input_shape,
    input_dtype: str,
    *,
    kernels: str = "xla",
    mask_threshold: Optional[float] = None,
    quantized: bool = False,
    stateful: bool = False,
    device: str = "",
) -> Tuple[str, dict]:
    """(key, meta) for one bucket executable. ``meta`` is the exact
    dict the key hashes — it is recorded in the entry header and
    re-verified at load, so a hash collision or a tampered file can
    never load as the wrong program. ``mask_threshold`` is key material
    because the serve-mask kernel bakes the threshold into the traced
    program (serve/engine.py)."""
    meta = {
        "engine_fingerprint": str(fingerprint),
        "bucket": int(bucket),
        "input_shape": [int(s) for s in input_shape],
        "input_dtype": str(input_dtype),
        "kernels": str(kernels),
        "mask_threshold": (
            None if mask_threshold is None else float(mask_threshold)
        ),
        "quantized": bool(quantized),
        "stateful": bool(stateful),
        "device": str(device),
    }
    blob = json.dumps(meta, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16], meta


def _note(result: str, key: str = "", detail: str = "") -> None:
    """One store event: the counter family + the flight ring (a
    skew-storm at relaunch must be diagnosable post-mortem)."""
    from distributedpytorch_tpu.obs import defs as obsm
    from distributedpytorch_tpu.obs import flight

    obsm.AOT_CACHE.labels(result=result).inc()
    fields = {"result": result, "key": key}
    if detail:
        fields["detail"] = detail[:200]
    flight.record("aot_cache", **fields)


#: Markers XLA stamps into an executable's text when any input buffer is
#: aliased to an output (the compiled form of ``jit(...,
#: donate_argnums=...)``). Shared with analysis/donation.py, which scans
#: the LOWERED (pre-compile) text for the same property statically.
DONATION_MARKERS = ("input_output_alias", "tf.aliasing_output")


def executable_donates(compiled) -> bool:
    """Does this compiled executable alias an input buffer into an
    output? Such an executable frees (or overwrites) an operand on
    every call — admitting one to the store hands every sibling
    process a use-after-free: serve replicas re-read their weights
    operand on each request, so the second request through a
    rehydrated donated executable reads poisoned memory (the
    CPU-backend SIGABRT class). Unreadable text counts as donating —
    the store must be able to PROVE cleanliness to admit."""
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — no proof, no admission
        return True
    return any(marker in text for marker in DONATION_MARKERS)


class AOTStore:
    """One store directory; flat ``<key>.aotx`` entries."""

    def __init__(self, root: str):
        self.root = os.path.abspath(str(root))
        # per-engine-build story (serve /stats); the process-wide view
        # is the dpt_aot_cache_total counter family
        self.stats = {"hit": 0, "miss": 0, "skew": 0}

    @classmethod
    def resolve(cls, aot_cache=None) -> Optional["AOTStore"]:
        """Explicit arg > ``$DPT_AOT_CACHE`` > disabled (None). An
        empty-string arg disables even with the env var set; an
        already-built store passes through."""
        if isinstance(aot_cache, cls):
            return aot_cache
        root = (
            aot_cache if aot_cache is not None else os.environ.get(ENV_VAR)
        )
        return cls(root) if root else None

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}{ENTRY_SUFFIX}")

    # -- persist -------------------------------------------------------------
    def save(self, key: str, meta: dict, compiled) -> Optional[str]:
        """Serialize ``compiled`` and atomically persist it under
        ``key``. Never raises outward: a store that cannot persist
        (disk full, unserializable executable) logs a note and the
        engine simply stays uncached."""
        if executable_donates(compiled):
            logger.warning(
                "aot store: refusing to admit %s — the executable "
                "aliases an input buffer to an output (donation); a "
                "rehydrating sibling would re-read a freed operand. "
                "Serving continues uncached; fix the donating jit "
                "wrapper (serve executables must lower through "
                "serve/engine.serve_jit, which never donates)",
                key,
            )
            return None
        try:
            from jax.experimental.serialize_executable import serialize

            blob, in_tree, out_tree = serialize(compiled)
            payload = pickle.dumps(
                (blob, in_tree, out_tree), protocol=pickle.HIGHEST_PROTOCOL
            )
            header = dict(meta)
            header.update(runtime_versions())
            header.update({
                "kind": ENTRY_KIND,
                "version": ENTRY_VERSION,
                "key": str(key),
                "created": round(time.time(), 3),
                "payload_bytes": len(payload),
            })
            hjson = json.dumps(header, sort_keys=True).encode()
            body = len(hjson).to_bytes(8, "big") + hjson + payload
            os.makedirs(self.root, exist_ok=True)
            path = self._path(key)
            tmp = f"{path}.tmp.{os.getpid()}.{next(_TMP_COUNTER)}"
            self._commit(tmp, path, body)
            return path
        except Exception as exc:  # noqa: BLE001 — persist is best-effort
            logger.warning(
                "aot store: failed to persist %s under %s (%s: %s) — "
                "serving continues, this start stays uncached",
                key, self.root, type(exc).__name__, exc,
            )
            return None

    def _commit(self, tmp: str, path: str, body: bytes) -> None:
        """tmp + footer + rename (the checkpoint.py writer idiom); the
        torn-write regression test aborts inside this seam."""
        with open(tmp, "wb") as f:
            f.write(body)
            f.write(_HASH_MAGIC)
            f.write(hashlib.sha256(body).digest())
        os.replace(tmp, path)

    # -- load ----------------------------------------------------------------
    def load(self, key: str, meta: dict, device):
        """The executable for ``key`` loaded onto ``device``, or None.
        No file = ``miss``; a file that is torn, schema-broken,
        runtime-skewed, or whose recorded identity disagrees with
        ``meta`` = ``skew`` — refused with a logged note, never loaded,
        never a crash. A hit bumps the entry's mtime (the ``gc`` LRU
        clock).

        ``device`` is the replica's device: a one-device executable
        deserialized without ``execution_devices`` is loaded across
        every device of the backend and then refuses its one-shard
        arguments — on any host with more than one device, and for
        every replica that is not device 0."""
        path = self._path(key)
        if not os.path.exists(path):
            self.stats["miss"] += 1
            _note("miss", key)
            return None
        try:
            header, payload = self._read_verified(path)
            reason = self._skew_reason(header, meta)
            if reason is None:
                from jax.experimental.serialize_executable import (
                    deserialize_and_load,
                )

                blob, in_tree, out_tree = pickle.loads(payload)
                with no_xla_compilation_cache():
                    compiled = deserialize_and_load(
                        blob, in_tree, out_tree,
                        execution_devices=[device],
                    )
            else:
                raise AOTEntryError(reason)
        except Exception as exc:  # noqa: BLE001 — every failure mode of
            # a cached entry is a refusal-with-note, not a serve outage
            self.stats["skew"] += 1
            logger.warning(
                "aot store: REFUSING cached entry %s (%s: %s) — "
                "recompiling this bucket and re-persisting",
                path, type(exc).__name__, exc,
            )
            _note("skew", key, f"{type(exc).__name__}: {exc}")
            return None
        try:
            os.utime(path, None)
        except OSError:
            pass
        self.stats["hit"] += 1
        _note("hit", key)
        return compiled

    def _read_verified(self, path: str) -> Tuple[dict, bytes]:
        """header + payload, integrity-checked against the sha256
        footer. Any structural problem raises :class:`AOTEntryError`."""
        with open(path, "rb") as f:
            raw = f.read()
        if (
            len(raw) <= _FOOTER_LEN
            or raw[-_FOOTER_LEN:-32] != _HASH_MAGIC
        ):
            raise AOTEntryError("missing integrity footer (torn write?)")
        body, digest = raw[:-_FOOTER_LEN], raw[-32:]
        if hashlib.sha256(body).digest() != digest:
            raise AOTEntryError(
                "content hash mismatch (torn write or bit rot)"
            )
        try:
            hlen = int.from_bytes(body[:8], "big")
            header = json.loads(body[8:8 + hlen].decode())
            payload = body[8 + hlen:]
        except (ValueError, UnicodeDecodeError) as exc:
            raise AOTEntryError(f"unparseable header: {exc}") from exc
        if not isinstance(header, dict):
            raise AOTEntryError("header is not an object")
        return header, payload

    @staticmethod
    def _skew_reason(header: dict, meta: dict) -> Optional[str]:
        """Why this entry must be refused, or None. Checks the entry
        schema, the compiling runtime vs this one, and the recorded key
        identity vs what the caller is about to serve — 'unverifiable'
        must not read as 'verified' (the check_profile rule)."""
        if (
            header.get("kind") != ENTRY_KIND
            or header.get("version") != ENTRY_VERSION
        ):
            return (
                f"entry schema {header.get('kind')!r} "
                f"v{header.get('version')!r} != {ENTRY_KIND!r} "
                f"v{ENTRY_VERSION}"
            )
        here = runtime_versions()
        for field in RUNTIME_FIELDS:
            if header.get(field) != here[field]:
                return (
                    f"compiled under {field}={header.get(field)!r} but "
                    f"this runtime is {field}={here[field]!r}"
                )
        for k, want in meta.items():
            if header.get(k) != want:
                return (
                    f"recorded {k}={header.get(k)!r} != expected "
                    f"{want!r} (key collision or tampered entry)"
                )
        return None

    # -- inspection / eviction ----------------------------------------------
    def ls(self) -> List[dict]:
        """One row per entry (header fields + size/mtime), oldest
        first. Unreadable entries list as ``{"corrupt": True}`` rows —
        ``ls`` is a diagnostic and must not crash on what ``load``
        would refuse."""
        rows: List[dict] = []
        try:
            names = sorted(
                n for n in os.listdir(self.root)
                if n.endswith(ENTRY_SUFFIX)
            )
        except OSError:
            return rows
        for name in names:
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
                header, _ = self._read_verified(path)
                rows.append({
                    **header,
                    "size_bytes": st.st_size,
                    "mtime": st.st_mtime,
                })
            except (OSError, AOTEntryError) as exc:
                rows.append({
                    "key": name[: -len(ENTRY_SUFFIX)],
                    "corrupt": True,
                    "error": str(exc),
                })
        rows.sort(key=lambda r: r.get("mtime", 0.0))
        return rows

    def gc(self, max_bytes: int) -> List[str]:
        """LRU-evict entries (oldest mtime first — hits bump mtime)
        until the store fits ``max_bytes``; returns evicted keys.
        Stale tmp files from killed writers are always swept."""
        evicted: List[str] = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return evicted
        for name in names:
            if ".tmp." in name:
                try:
                    os.unlink(os.path.join(self.root, name))
                except OSError:
                    pass
        entries = []
        total = 0
        for name in names:
            if not name.endswith(ENTRY_SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, path, name))
            total += st.st_size
        entries.sort()
        for mtime, size, path, name in entries:
            if total <= max_bytes:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            key = name[: -len(ENTRY_SUFFIX)]
            evicted.append(key)
            _note("evicted", key)
        return evicted


# -- CLI: python -m distributedpytorch_tpu aot {warm,ls,gc} ------------------
def _require_root(args) -> Optional[str]:
    root = args.aot_cache or os.environ.get(ENV_VAR)
    if not root:
        print(
            "no store directory: pass --aot-cache DIR or set "
            f"${ENV_VAR}", flush=True,
        )
    return root


def _cmd_warm(args) -> int:
    """Prewarm a checkpoint's whole bucket ladder into the store — the
    fleet then cold-starts load-bound. Same identity flags as the serve
    CLI, because the key is the served identity."""
    root = _require_root(args)
    if not root:
        return 2
    from distributedpytorch_tpu.serve.engine import engine_from_checkpoint

    engine = engine_from_checkpoint(
        args.checkpoint,
        checkpoint_dir=args.checkpoint_dir,
        image_size=tuple(args.image_size),
        model_arch=args.model_arch,
        model_widths=(
            tuple(args.model_widths) if args.model_widths else None
        ),
        s2d_levels=args.s2d_levels,
        quantize=args.quantize,
        bucket_sizes=tuple(args.buckets),
        replicas=args.replicas,
        threshold=args.threshold,
        kernels=args.kernels,
        host_cache_mb=0,
        aot_cache=root,
    )
    print(json.dumps({"warmed": engine.aot_cache_stats}, indent=2))
    return 0


def _cmd_ls(args) -> int:
    root = _require_root(args)
    if not root:
        return 2
    rows = AOTStore(root).ls()
    if args.json:
        print(json.dumps(rows, indent=2))
        return 0
    print(f"{len(rows)} entries in {root}")
    for r in rows:
        if r.get("corrupt"):
            print(f"  {r['key']}  CORRUPT: {r.get('error', '')}")
            continue
        shape = "x".join(str(s) for s in r.get("input_shape", []))
        print(
            f"  {r.get('key')}  fp={r.get('engine_fingerprint')}  "
            f"shape={shape}  kernels={r.get('kernels')}  "
            f"dev={r.get('device')}  jaxlib={r.get('jaxlib')}  "
            f"{r.get('size_bytes', 0) / 2**20:.1f} MiB"
        )
    return 0


def _cmd_gc(args) -> int:
    root = _require_root(args)
    if not root:
        return 2
    evicted = AOTStore(root).gc(int(args.max_gb * 2**30))
    print(json.dumps({"evicted": evicted, "max_gb": args.max_gb}))
    return 0


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu aot",
        description=(
            "Manage the content-addressed AOT executable store "
            "(docs/PERFORMANCE.md 'AOT executable store')."
        ),
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    warm = sub.add_parser(
        "warm", help="compile-and-persist a checkpoint's bucket ladder"
    )
    warm.add_argument("--checkpoint", "-c", required=True)
    warm.add_argument("--checkpoint-dir", default="./checkpoints")
    warm.add_argument("--image-size", type=int, nargs=2,
                      default=(960, 640), metavar=("W", "H"))
    warm.add_argument("--model", dest="model_arch", default="unet")
    warm.add_argument("--model-widths", type=int, nargs="+", default=None)
    warm.add_argument("--s2d-levels", type=int, default=-1)
    warm.add_argument("--quantize", default=None)
    warm.add_argument("--kernels", default="xla")
    warm.add_argument("--threshold", "-t", type=float, default=0.5)
    warm.add_argument("--buckets", type=int, nargs="+",
                      default=(1, 2, 4, 8))
    warm.add_argument("--replicas", type=int, default=1)
    warm.add_argument("--aot-cache", default=None)
    warm.set_defaults(fn=_cmd_warm)

    ls = sub.add_parser("ls", help="list store entries (oldest first)")
    ls.add_argument("--aot-cache", default=None)
    ls.add_argument("--json", action="store_true")
    ls.set_defaults(fn=_cmd_ls)

    gc = sub.add_parser(
        "gc", help="LRU-evict entries until the store fits --max-gb"
    )
    gc.add_argument("--max-gb", type=float, required=True)
    gc.add_argument("--aot-cache", default=None)
    gc.set_defaults(fn=_cmd_gc)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    import sys

    sys.exit(main())
