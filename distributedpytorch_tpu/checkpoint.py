"""Checkpointing: native full-state save/resume + reference .pth interop.

The reference saves a bare ``state_dict`` once, after the final epoch, and
can only reload weights — no optimizer/scheduler/step state, so no true
resume (reference utils/train_utils.py:88, train.py:42-43; SURVEY.md §5).
This module fixes that:

  * `save_checkpoint` / `load_checkpoint` — the native format: one msgpack
    file holding params, Adam state, plateau-scheduler state, step and epoch
    counters. Written atomically (tmp + rename) so a crash mid-write never
    corrupts the previous checkpoint. Device arrays are gathered to host
    numpy first, so a sharded (DDP / pipeline) run saves exactly once per
    process-0 without layout baggage — restored params can be re-placed
    under any strategy's sharding.
  * `export_reference_pth` / `import_reference_pth` — interop shim keyed to
    the reference's parameter names (``encoder.conv1.conv_block.0.weight``…,
    reference model/unet_parts.py:9-14, 22-26, 46-54, unet_model.py:7-10)
    with NHWC↔NCHW kernel transposes. Import tolerates the DDP ``module.``
    key prefix the reference leaks into its DDP checkpoints (quirk 9).

Resilience (docs/RELIABILITY.md):

  * **multi-host-safe gather** — `_to_host` allgathers each leaf that is
    sharded across processes (FSDP/TP on a pod: not fully addressable, so
    a bare ``device_get`` would fail); the gather is COLLECTIVE, so every
    process must reach the save path (train/loop.py builds the payload on
    all ranks and gates only the file write to rank 0);
  * **integrity footer** — every file carries a sha256 of its msgpack
    payload; restore verifies it and refuses torn/corrupt bytes with
    :class:`CheckpointCorruptError` (legacy footer-less files still load);
  * **retention + fallback** — saves retain the newest ``keep`` files
    (``x.ckpt``, ``x.ckpt.1``, …) and `load_checkpoint` automatically
    falls back to the newest INTACT retained file, so a crash mid-write
    can no longer strand a restart on a corrupt checkpoint.
"""

from __future__ import annotations

import hashlib
import itertools
import logging
import os
import threading
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import flax.serialization
import jax
import numpy as np

from distributedpytorch_tpu.utils import faults

logger = logging.getLogger(__name__)

CKPT_VERSION = 1

# Integrity footer: payload bytes + MAGIC + sha256(payload). Fixed-size
# trailer so the reader can split it off without parsing; files written
# before the footer existed simply lack the MAGIC and skip verification.
_HASH_MAGIC = b"DPT-SHA256:"
_FOOTER_LEN = len(_HASH_MAGIC) + 32


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed its integrity check (hash mismatch or
    unparseable payload) — torn write, bit rot, or truncation."""


def needs_collective_gather(x) -> bool:
    """True for a leaf sharded ACROSS processes (FSDP/TP state on a pod):
    not materializable by any single host, so `_to_host` must allgather
    it — a collective every rank participates in. ONE definition shared
    with the trainer's save gating (train/loop.py `_save_needs_all_ranks`):
    if the two ever disagreed, non-main ranks would skip a payload build
    `_to_host` treats as collective and every rank would hang."""
    return (
        isinstance(x, jax.Array)
        and not x.is_fully_addressable
        and not x.is_fully_replicated
    )


def _to_host(tree):
    # ONE device_get for the whole tree: per-leaf pulls are a synchronous
    # device→host round trip each, ~140 of them per save. Leaves sharded
    # ACROSS processes (FSDP/TP state on a pod) are not fully addressable
    # — device_get cannot materialize them — so those are allgathered per
    # leaf instead (a collective: every process must call, in the same
    # leaf order — jax.tree flattening order is deterministic). Fully
    # replicated global arrays keep the cheap device_get path.
    leaves, treedef = jax.tree.flatten(tree)
    needs_gather = needs_collective_gather

    if not any(needs_gather(x) for x in leaves):
        return jax.tree.map(np.asarray, jax.device_get(tree))
    from jax.experimental import multihost_utils

    # one batched device_get for ALL non-gathered leaves (per-leaf pulls
    # would reintroduce the round trips the fast path above exists to
    # avoid); only the genuinely sharded leaves pay a collective each
    plain_idx = [i for i, x in enumerate(leaves) if not needs_gather(x)]
    plain = jax.device_get([leaves[i] for i in plain_idx])
    out: list = list(leaves)
    for i, v in zip(plain_idx, plain):
        out[i] = np.asarray(v)
    for i, x in enumerate(leaves):
        if needs_gather(x):
            out[i] = np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return jax.tree.unflatten(treedef, out)


def save_topology() -> dict:
    """The mesh/sharding topology a checkpoint is being saved under —
    recorded in the manifest so restore can SAY it is resharding
    (N→M processes, different mesh shape) rather than silently assuming
    an identical layout. Restore never *requires* a topology match:
    `_to_host` gathers every leaf to a full host array at save time, so
    the file is layout-free and re-places under any current mesh
    (`Trainer._restore` logs the reshard when the topologies differ)."""
    return {
        "process_count": int(jax.process_count()),
        "device_count": int(jax.device_count()),
    }


def _build_payload(
    params,
    opt_state=None,
    scheduler_state: Optional[dict] = None,
    step: int = 0,
    epoch: int = 0,
    records_state: Optional[dict] = None,
    model_state=None,
    train_meta: Optional[dict] = None,
    topology: Optional[dict] = None,
) -> dict:
    """Snapshot everything to HOST values. This is the only part of a save
    that must run on the trainer thread: device buffers are donated into
    the next dispatched step, so the device_get cannot be deferred."""
    return {
        "version": CKPT_VERSION,
        # saving-time mesh topology (strategy name, mesh axis sizes,
        # process/device counts) — informational manifest for the
        # mesh-resharding restore path; absent in older checkpoints
        "topology": {**save_topology(), **(topology or {})},
        # small scalar trainer state that must survive resume (best val
        # metrics for --save-best, early-stop patience counter) — plain
        # msgpack-able dict, absent in older checkpoints
        "train_meta": train_meta,
        "params": flax.serialization.to_state_dict(_to_host(params)),
        "opt_state": flax.serialization.to_state_dict(_to_host(opt_state))
        if opt_state is not None
        else None,
        "scheduler": scheduler_state,
        "step": int(step),
        "epoch": int(epoch),
        # metric history (LossRecords.state_dict): a resumed run must append
        # to the run's loss curves, not overwrite the pickles with only its
        # post-resume rows
        "records": records_state,
        # non-trainable model collections (BatchNorm running stats) for
        # stateful models; None otherwise
        "model_state": flax.serialization.to_state_dict(_to_host(model_state))
        if model_state is not None
        else None,
    }


_TMP_COUNTER = itertools.count()

# ONE lock around every rotate/rename/prune of a retention chain: the
# chain is shared mutable state between the async writer thread, any
# synchronous save (--sync-checkpoint, tests, tools), and external
# pruning (a lowered --keep-checkpoints). Without it a prune can delete
# the `path.1` slot an in-flight save just rotated its predecessor into
# — exactly the file restore's fallback would need if that save's
# rename then failed. Held only across cheap filesystem metadata ops
# (the payload write itself happens to a unique tmp name outside any
# contention), so serializing here costs nothing measurable.
_RETENTION_LOCK = threading.Lock()


def _rotate_retained(path: str, keep: int) -> None:
    """Shift the retained chain one slot: ``path`` → ``path.1`` → … up to
    ``path.(keep-1)``. ``keep <= 1`` keeps only the live file (no chain)."""
    if keep <= 1 or not os.path.exists(path):
        return
    for i in range(keep - 1, 0, -1):
        src = path if i == 1 else f"{path}.{i - 1}"
        if os.path.exists(src):
            os.replace(src, f"{path}.{i}")


def _prune_retained(path: str, keep: int) -> None:
    # bounded scan (not glob): retained suffixes are small ints and a
    # lowered --keep-checkpoints may leave holes above the new limit
    for i in range(max(1, keep), 64):
        stale = f"{path}.{i}"
        if os.path.exists(stale):
            os.remove(stale)


def prune_retained(path: str, keep: int) -> None:
    """Trim ``path``'s retention chain to the newest ``keep`` files —
    the external entry point (tools, a lowered ``--keep-checkpoints``).
    Takes the retention lock, so it can never race an in-flight
    `save_checkpoint_async` write's rotate/rename out from under it
    (tests/test_faults.py races exactly this)."""
    with _RETENTION_LOCK:
        _prune_retained(path, keep)


def retained_checkpoints(path: str) -> List[str]:
    """The retention chain on disk, newest first (``path`` itself, then
    ``path.1``, …) — the restore fallback order."""
    out = [path] if os.path.exists(path) else []
    for i in range(1, 64):
        cand = f"{path}.{i}"
        if os.path.exists(cand):
            out.append(cand)
    return out


def _write_payload(path: str, payload: dict, keep: int = 1) -> str:
    """Serialize + integrity footer + atomic write (tmp + rename: a crash
    mid-write never corrupts the previous checkpoint), rotating the
    retained chain first so the previous file survives as ``path.1``.
    Unique tmp names: queued async saves of the same path must not
    clobber each other's tmp files."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    blob = flax.serialization.msgpack_serialize(payload)
    if faults.fire("ckpt_write", epoch=payload.get("epoch")):
        # Simulate the failure retention exists for: a write that died
        # half-way AND tore the destination (non-atomic filesystem, power
        # loss mid-rename). Rotate like a real save, leave torn bytes at
        # `path`, and raise — restore must fall back to `path.1`.
        with _RETENTION_LOCK:
            _rotate_retained(path, keep)
            with open(path, "wb") as f:
                f.write(blob[: max(1, len(blob) // 2)])
        raise faults.InjectedFault(
            f"injected ckpt_write fault: torn file left at {path}"
        )
    tmp = f"{path}.tmp.{os.getpid()}.{next(_TMP_COUNTER)}"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.write(_HASH_MAGIC)
        f.write(hashlib.sha256(blob).digest())
    with _RETENTION_LOCK:
        _rotate_retained(path, keep)
        os.replace(tmp, path)
        _prune_retained(path, keep)
    return path


def _read_verified(path: str) -> dict:
    """Read + integrity-check one checkpoint file. Hash mismatch and
    unparseable payloads (torn legacy files) both raise
    :class:`CheckpointCorruptError`; footer-less legacy files load
    unverified."""
    with open(path, "rb") as f:
        blob = f.read()
    if (
        len(blob) > _FOOTER_LEN
        and blob[-_FOOTER_LEN:-32] == _HASH_MAGIC
    ):
        body, digest = blob[:-_FOOTER_LEN], blob[-32:]
        if hashlib.sha256(body).digest() != digest:
            raise CheckpointCorruptError(
                f"{path}: content hash mismatch (torn write or bit rot)"
            )
        blob = body
    try:
        return flax.serialization.msgpack_restore(blob)
    except Exception as exc:
        raise CheckpointCorruptError(f"{path}: unreadable payload: {exc}") from exc


def verify_checkpoint(path: str) -> bool:
    """True iff ``path`` parses and (when a footer is present) its hash
    verifies."""
    try:
        _read_verified(path)
        return True
    except CheckpointCorruptError:
        return False


def save_checkpoint(
    path: str,
    params,
    opt_state=None,
    scheduler_state: Optional[dict] = None,
    step: int = 0,
    epoch: int = 0,
    records_state: Optional[dict] = None,
    model_state=None,
    train_meta: Optional[dict] = None,
    keep: int = 1,
    write: bool = True,
    topology: Optional[dict] = None,
) -> None:
    """``write=False`` builds the payload WITHOUT touching disk — the
    multi-process contract: the host snapshot inside `_build_payload` is
    collective when state is sharded across processes, so every rank
    calls this and only rank 0 passes ``write=True`` (train/loop.py)."""
    payload = _build_payload(
        params,
        opt_state,
        scheduler_state,
        step,
        epoch,
        records_state,
        model_state,
        train_meta,
        topology,
    )
    if write:
        _write_payload(path, payload, keep=keep)


# ---------------------------------------------------------------------------
# Async saves: ONE background writer thread, saves applied in submission
# order (so <tag>.ckpt always ends at the newest queued snapshot). The
# thread is a daemon started on first use: serialization + disk I/O are the
# multi-second part of a save (the device_get is not — see _build_payload)
# and nothing in the step loop depends on them.
# ---------------------------------------------------------------------------

_writer_lock = threading.Lock()
_writer_queue = None  # created lazily; holds (Future, path, payload)


def _writer_loop(q):
    while True:
        fut, path, payload, keep = q.get()
        if not fut.set_running_or_notify_cancel():
            continue
        try:
            fut.set_result(_write_payload(path, payload, keep=keep))
        except BaseException as exc:  # surfaced via Future.result()
            fut.set_exception(exc)


def save_checkpoint_async(
    path: str,
    params,
    opt_state=None,
    scheduler_state: Optional[dict] = None,
    step: int = 0,
    epoch: int = 0,
    records_state: Optional[dict] = None,
    model_state=None,
    train_meta: Optional[dict] = None,
    keep: int = 1,
    write: bool = True,
    topology: Optional[dict] = None,
) -> Optional[Future]:
    """`save_checkpoint` with the serialize+write half on the background
    writer: snapshots state to host NOW (cheap single device_get — also
    the correctness boundary, the next step donates these buffers, AND
    the collective boundary: a cross-process allgather must run on the
    caller thread in rank-lockstep, never on the writer), returns a
    Future that resolves to ``path`` when the file is durably in place.
    ``write=False`` (non-main ranks) participates in the snapshot and
    returns None. Callers must eventually ``result()`` the future (the
    trainer drains its list when training ends) or a failed write would
    pass silently.
    """
    global _writer_queue
    payload = _build_payload(
        params,
        opt_state,
        scheduler_state,
        step,
        epoch,
        records_state,
        model_state,
        train_meta,
        topology,
    )
    if not write:
        return None
    with _writer_lock:
        if _writer_queue is None:
            import queue as queue_mod

            _writer_queue = queue_mod.Queue()
            threading.Thread(
                target=_writer_loop,
                args=(_writer_queue,),
                daemon=True,
                name="dpt-ckpt-writer",
            ).start()
    fut: Future = Future()
    _writer_queue.put((fut, path, payload, keep))
    return fut


def resolve_checkpoint(name: str, checkpoint_dir: str = "./checkpoints") -> str:
    """Resolve a checkpoint reference to an existing file path.

    Accepts an explicit path (``./ckpts/run.ckpt``), a bare method name
    (``DP`` → ``<dir>/DP.ckpt``, falling back to ``<dir>/DP.pth``), or an
    extension-suffixed name (``DP.pth`` → resolved inside `checkpoint_dir`,
    matching the trainer's ``-c``/-l`` semantics, train/loop.py). Raises
    FileNotFoundError naming the primary candidate when nothing exists.
    """
    if os.path.isfile(name):  # isfile: a same-named DIRECTORY must not shadow
        return name
    base, explicit_ext = name, None
    for ext in (".ckpt", ".pth"):
        if base.endswith(ext):
            base, explicit_ext = base[: -len(ext)], ext
            break
    # an explicitly-suffixed name tries ONLY that format — 'DP.pth' must
    # never silently load DP.ckpt when both exist
    exts = (explicit_ext,) if explicit_ext else (".ckpt", ".pth")
    for ext in exts:
        cand = os.path.join(checkpoint_dir, f"{base}{ext}")
        if os.path.isfile(cand):
            return cand
        if ext == ".ckpt" and retained_checkpoints(cand):
            # live slot empty but the retention chain survives (a crash
            # between rotate and rename): resolvable — load_checkpoint's
            # fallback walks the chain from the primary path
            return cand
    raise FileNotFoundError(os.path.join(checkpoint_dir, f"{base}{exts[0]}"))


def read_payload(path: str, fallback: bool = True) -> dict:
    """The newest INTACT candidate's raw payload dict (retention-chain
    walk + integrity check — exactly `load_checkpoint`'s file selection,
    WITHOUT binding any target structures). The restore path reads this
    once, inspects the manifest to build policy-correct targets, then
    hands the same payload back to `load_checkpoint` — a multi-GB file
    must not be read and deserialized twice per resume."""
    candidates = retained_checkpoints(path) if fallback else [path]
    if not candidates:
        candidates = [path]
    payload = None
    for cand in candidates:
        try:
            payload = _read_verified(cand)
            if cand != path:
                logger.warning(
                    "checkpoint %s is corrupt or missing — restored the "
                    "newest intact retained file %s instead",
                    path, cand,
                )
            break
        except CheckpointCorruptError as exc:
            logger.warning("checkpoint integrity failure: %s", exc)
    if payload is None:
        raise CheckpointCorruptError(
            f"no intact checkpoint among {candidates} — every candidate "
            "failed its integrity check"
        )
    return payload


def peek_topology(path: str, fallback: bool = True) -> Optional[dict]:
    """The saving-time topology manifest (strategy/mesh/process counts and
    the ``precision`` policy name) of the checkpoint `load_checkpoint`
    would restore — WITHOUT building any target structures. None for
    pre-manifest checkpoints (and raises what `load_checkpoint` would
    raise when no intact candidate exists)."""
    return read_payload(path, fallback=fallback).get("topology")


def load_weights(path: str, params_template):
    """Params from either checkpoint format: native full-state ``.ckpt`` or
    reference ``.pth`` (NHWC↔NCHW transposes, ``module.`` prefix tolerated).
    The format rule lives here only — trainer resume and inference share it."""
    if path.endswith(".pth"):
        return import_reference_pth(path, params_template)
    return load_checkpoint(path, params_template, None)["params"]


def load_checkpoint(
    path: str,
    params_target,
    opt_state_target=None,
    model_state_target=None,
    fallback: bool = True,
    payload: Optional[dict] = None,
) -> Dict[str, Any]:
    """Restore a checkpoint into the given target structures.

    Every file is integrity-checked (`_read_verified`); when ``path``
    itself is corrupt and ``fallback`` is on, restore walks the retention
    chain (``path.1``, ``path.2``, …) to the newest INTACT file — so a
    crash mid-write costs one save interval of progress, not the run
    (`fit_with_restarts` then resumes from the fallback's epoch). All
    candidates corrupt raises :class:`CheckpointCorruptError`.

    ``payload`` short-circuits the file read: a caller that already ran
    `read_payload` (the trainer's policy-aware restore peeks the
    manifest to build its targets) binds against that dict instead of
    reading and deserializing the file a second time.

    Returns ``{'params', 'opt_state', 'scheduler', 'step', 'epoch',
    'records', 'model_state'}``; `opt_state` is None when the checkpoint
    predates it or no target given, `records` (metric history) and
    `model_state` (BatchNorm stats) likewise.
    """
    if payload is None:
        payload = read_payload(path, fallback=fallback)
    out = {
        "params": flax.serialization.from_state_dict(params_target, payload["params"]),
        "opt_state": None,
        "scheduler": payload.get("scheduler"),
        "step": int(payload.get("step", 0)),
        "epoch": int(payload.get("epoch", 0)),
        "records": payload.get("records"),
        "model_state": None,
        "train_meta": payload.get("train_meta"),
        # saving-time mesh topology (None for pre-elastic checkpoints):
        # the restore side compares it against the CURRENT topology and
        # reports a resharding restore (train/loop.py `_restore`)
        "topology": payload.get("topology"),
    }
    if payload.get("opt_state") is not None and opt_state_target is not None:
        out["opt_state"] = flax.serialization.from_state_dict(
            opt_state_target, payload["opt_state"]
        )
    if payload.get("model_state") is not None and model_state_target is not None:
        out["model_state"] = flax.serialization.from_state_dict(
            model_state_target, payload["model_state"]
        )
    return out


# ---------------------------------------------------------------------------
# Reference .pth interop
# ---------------------------------------------------------------------------

# (flax module path) -> (reference state_dict stem). conv1/conv2 inside a
# ConvBlock map to Sequential indices 0/2 (reference unet_parts.py:9-14).
_BLOCK_MAPS: Tuple[Tuple[Tuple[str, ...], str], ...] = tuple(
    [(("encoder", f"block{i}"), f"encoder.conv{i}") for i in range(1, 5)]
    + [(("mid",), "mid")]
    + [(("decoder", f"block{i}"), f"decoder.conv{i}") for i in range(1, 5)]
)


def _flatten_params(params) -> Dict[Tuple[str, ...], np.ndarray]:
    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(prefix + (k,), v)
        else:
            flat[prefix] = np.asarray(jax.device_get(node))

    walk((), flax.serialization.to_state_dict(params))
    return flat


def _kernel_to_torch(arr: np.ndarray, transposed: bool) -> np.ndarray:
    """flax (kh, kw, I, O) → torch conv (O, I, kh, kw) / ConvTranspose
    (I, O, kh, kw) with a spatial flip — lax.conv_transpose correlates with
    the mirrored kernel relative to torch's scatter semantics (validated
    against torch numerics in tests/test_checkpoint.py)."""
    if transposed:
        return arr[::-1, ::-1].transpose(2, 3, 0, 1)
    return arr.transpose(3, 2, 0, 1)


def _kernel_from_torch(arr: np.ndarray, transposed: bool) -> np.ndarray:
    if transposed:
        return arr.transpose(2, 3, 0, 1)[::-1, ::-1]
    return arr.transpose(2, 3, 1, 0)


def _rebuild_from_named(target, name_map, cleaned, transform):
    """Rebuild a pytree shaped like ``target`` by looking each flat path up
    in ``cleaned`` via ``name_map`` and applying ``transform(path, arr)``.
    Shared by both .pth families (reference course model / milesial)."""
    flat = {}
    for path in _flatten_params(target):
        flat[path] = np.ascontiguousarray(transform(path, cleaned[name_map[path]]))

    def walk(prefix, node):
        if isinstance(node, dict):
            return {k: walk(prefix + (k,), v) for k, v in node.items()}
        return flat[prefix]

    as_dict = walk((), flax.serialization.to_state_dict(target))
    return flax.serialization.from_state_dict(target, as_dict)


def _save_pth(state_dict: Dict[str, np.ndarray], path: str) -> None:
    import torch

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    torch.save({k: torch.from_numpy(v.copy()) for k, v in state_dict.items()}, path)


def _load_pth(path: str) -> Dict[str, np.ndarray]:
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.numpy() for k, v in sd.items() if hasattr(v, "numpy")}


def _strip_module_prefix(state_dict: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """DDP saves ``module.``-prefixed keys (reference quirk 9)."""
    return {
        (k[len("module.") :] if k.startswith("module.") else k): np.asarray(v)
        for k, v in state_dict.items()
    }


def _name_map() -> Dict[Tuple[str, ...], str]:
    """flax param path → reference tensor name."""
    m: Dict[Tuple[str, ...], str] = {}
    for flax_path, ref_stem in _BLOCK_MAPS:
        for conv, seq_idx in (("conv1", 0), ("conv2", 2)):
            m[flax_path + (conv, "kernel")] = f"{ref_stem}.conv_block.{seq_idx}.weight"
            m[flax_path + (conv, "bias")] = f"{ref_stem}.conv_block.{seq_idx}.bias"
    for i in range(1, 5):
        m[("decoder", f"upconv{i}", "kernel")] = f"decoder.deconv{i}.weight"
        m[("decoder", f"upconv{i}", "bias")] = f"decoder.deconv{i}.bias"
    m[("segmap", "kernel")] = "segmap.weight"
    m[("segmap", "bias")] = "segmap.bias"
    return m


def _ref_is_transposed(path: Tuple[str, ...]) -> bool:
    return "upconv" in path[-2]


def export_reference_state_dict(params) -> Dict[str, np.ndarray]:
    """flax params (NHWC kernels) → reference-named dict (NCHW layouts,
    see _kernel_to_torch)."""
    names = _name_map()
    out: Dict[str, np.ndarray] = {}
    for path, arr in _flatten_params(params).items():
        if path[-1] == "kernel":
            arr = _kernel_to_torch(arr, _ref_is_transposed(path))
        out[names[path]] = np.ascontiguousarray(arr)
    return out


def import_reference_state_dict(
    state_dict: Dict[str, np.ndarray], params_target
):
    """Reference-named (possibly ``module.``-prefixed, quirk 9) dict → flax
    params shaped like `params_target`."""

    def transform(path, arr):
        if path[-1] == "kernel":
            return _kernel_from_torch(arr, _ref_is_transposed(path))
        return arr

    return _rebuild_from_named(
        params_target, _name_map(), _strip_module_prefix(state_dict), transform
    )


def export_reference_pth(params, path: str) -> None:
    """Write a real torch ``.pth`` loadable by the reference's
    ``model.load_state_dict(torch.load(...))`` (reference train.py:43)."""
    _save_pth(export_reference_state_dict(params), path)


def import_reference_pth(path: str, params_target):
    return import_reference_state_dict(_load_pth(path), params_target)


# ---------------------------------------------------------------------------
# milesial/Pytorch-UNet .pth interop (the public upstream family)
# ---------------------------------------------------------------------------
#
# torch module layout (milesial/Pytorch-UNet unet_parts.py): DoubleConv =
# Sequential(Conv2d, BatchNorm2d, ReLU, Conv2d, BatchNorm2d, ReLU) →
# tensor stems double_conv.{0,1,3,4}; Down wraps it as maxpool_conv.1;
# Up holds `up` (ConvTranspose2d) + `conv` (DoubleConv); OutConv holds
# `conv`. Checkpoints published by that repo load here directly — the
# strongest migration path for its users.


def _milesial_maps(n_levels: int):
    """(flax params path → torch name, flax batch_stats path → torch name)
    for a milesial model with ``n_levels`` width entries (stem + n−1 downs).
    """
    pmap: Dict[Tuple[str, ...], str] = {}
    smap: Dict[Tuple[str, ...], str] = {}

    def double_conv(flax_prefix: Tuple[str, ...], torch_stem: str):
        for conv, bn, c_idx, b_idx in (("conv1", "bn1", 0, 1), ("conv2", "bn2", 3, 4)):
            pmap[flax_prefix + (conv, "kernel")] = f"{torch_stem}.{c_idx}.weight"
            pmap[flax_prefix + (bn, "scale")] = f"{torch_stem}.{b_idx}.weight"
            pmap[flax_prefix + (bn, "bias")] = f"{torch_stem}.{b_idx}.bias"
            smap[flax_prefix + (bn, "mean")] = f"{torch_stem}.{b_idx}.running_mean"
            smap[flax_prefix + (bn, "var")] = f"{torch_stem}.{b_idx}.running_var"

    double_conv(("inc",), "inc.double_conv")
    for i in range(1, n_levels):
        double_conv((f"down{i}", "conv"), f"down{i}.maxpool_conv.1.double_conv")
    for i in range(1, n_levels):
        pmap[(f"up{i}", "up", "kernel")] = f"up{i}.up.weight"
        pmap[(f"up{i}", "up", "bias")] = f"up{i}.up.bias"
        double_conv((f"up{i}", "conv"), f"up{i}.conv.double_conv")
    pmap[("outc", "kernel")] = "outc.conv.weight"
    pmap[("outc", "bias")] = "outc.conv.bias"
    return pmap, smap


def _milesial_levels(params) -> int:
    as_dict = flax.serialization.to_state_dict(params)
    return 1 + sum(1 for k in as_dict if k.startswith("down"))


def export_milesial_state_dict(params, batch_stats) -> Dict[str, np.ndarray]:
    """flax milesial variables → torch-named state dict (NCHW layouts via
    _kernel_to_torch; ``num_batches_tracked`` zeros included so torch's
    strict ``load_state_dict`` accepts it)."""
    pmap, smap = _milesial_maps(_milesial_levels(params))
    out: Dict[str, np.ndarray] = {}
    for path, arr in _flatten_params(params).items():
        if path[-1] == "kernel":
            arr = _kernel_to_torch(arr, transposed=path[-2] == "up")
        out[pmap[path]] = np.ascontiguousarray(arr)
    for path, arr in _flatten_params(batch_stats).items():
        out[smap[path]] = np.ascontiguousarray(arr)
        out[smap[path].rsplit(".", 1)[0] + ".num_batches_tracked"] = np.asarray(
            0, np.int64
        )
    return out


def import_milesial_state_dict(
    state_dict: Dict[str, np.ndarray], params_target, stats_target
):
    """torch-named milesial dict → (params, batch_stats) shaped like the
    given targets. Accepts DDP's ``module.`` prefix like the UNet path."""
    cleaned = _strip_module_prefix(state_dict)
    pmap, smap = _milesial_maps(_milesial_levels(params_target))

    def p_transform(path, arr):
        if path[-1] == "kernel":
            return _kernel_from_torch(arr, transposed=path[-2] == "up")
        return arr

    return (
        _rebuild_from_named(params_target, pmap, cleaned, p_transform),
        _rebuild_from_named(stats_target, smap, cleaned, lambda path, arr: arr),
    )


def export_milesial_pth(params, batch_stats, path: str) -> None:
    _save_pth(export_milesial_state_dict(params, batch_stats), path)


def import_milesial_pth(path: str, params_target, stats_target):
    return import_milesial_state_dict(
        _load_pth(path), params_target, stats_target
    )
