"""The replica executor: AOT-compiled eval executables per bucket shape,
replicated data-parallel over the mesh's devices.

**AOT, not JIT.** ``jax.jit`` compiles on first call — a 20-40 s stall
on TPU that would land on whichever unlucky request first rides each
bucket shape. The serving tier instead compiles every (bucket × replica)
executable at *startup* via the same ``.lower(...).compile()`` path the
analyzer's ``--hlo`` tier exercises (analysis/collectives.py): inputs
are ``ShapeDtypeStruct``s carrying a ``SingleDeviceSharding``, so each
executable is built for — and pinned to — its replica's device, and the
first request pays exactly zero compiler time. A compiled executable
also *rejects* any shape it wasn't built for, which converts a bucket
accounting bug from silent recompilation into a loud TypeError.

**Replica groups.** Serving is embarrassingly data-parallel: N devices
serve N concurrent buckets with no cross-device collective (the static
preflight accordingly treats serve configs as non-collective). Each
replica holds its own device-resident copy of the weights and its own
per-bucket executables; the server round-robins flushed buckets across
free replicas. ``replicas`` clamps to the devices actually present, so
the same config serves a laptop CPU and an 8-chip host.

**Host-side decode cache.** Path-keyed requests decode through the PR-1
``SampleCache`` — the serving analogue of Clipper's prediction-adjacent
caching: repeated traffic over the same objects (the common case behind
a CDN miss storm) skips PIL/libjpeg entirely on the request path.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from distributedpytorch_tpu.data.dataset import SampleCache
from distributedpytorch_tpu.serve.bucketing import BucketPlanner
from distributedpytorch_tpu.serve.infer import (
    InferenceBundle,
    bundle_variables,
    make_forward,
    postprocess_mask,
    preprocess_image,
)

logger = logging.getLogger(__name__)


def serve_jit(fn):
    """The engine's ONE jit wrapper: every serve executable — every
    bucket of every replica, and therefore every entry admitted to the
    AOT store — lowers through here. It must NEVER donate: serve
    executables re-read their weights operand on every request, and a
    store-shared executable additionally re-reads buffers that sibling
    processes rehydrate — a donated operand is freed after the first
    call and the next request reads poisoned memory (the CPU-backend
    SIGABRT class). Kept as a named module-level seam so the donation
    pass (analysis/donation.py) can lower THROUGH the exact wrapper the
    engine uses, and its mutation tests can donate here and prove the
    pass catches it."""
    import jax

    return jax.jit(fn)


@dataclasses.dataclass
class Replica:
    """One device's serving state: weights resident on ``device`` and one
    compiled executable per bucket size. ``weights_version`` tracks which
    hot-swap generation this replica serves (0 = the startup weights) —
    during a rollout canary the groups legitimately diverge."""

    index: int
    device: object
    sharding: object
    variables: object
    compiled: Dict[int, object]
    weights_version: int = 0


class ServeEngine:
    """Build with an :class:`InferenceBundle` (checkpoint path) or raw
    ``(model, params, model_state)`` pieces (tests / bench fresh-init)."""

    def __init__(
        self,
        model,
        params,
        model_state,
        input_hw: Tuple[int, int],
        bucket_sizes: Sequence[int] = (1, 2, 4, 8),
        replicas: int = 1,
        threshold: float = 0.5,
        host_cache_mb: int = 0,
        channels: int = 3,
        quantized: bool = False,
        kernels="xla",
        aot_cache=None,
        engine_fingerprint: Optional[str] = None,
    ):
        import jax

        from distributedpytorch_tpu.ops.kernels import get_kernel_policy
        from distributedpytorch_tpu.utils.aotstore import AOTStore

        self.planner = BucketPlanner(bucket_sizes)
        self.model = model
        self.input_hw = (int(input_hw[0]), int(input_hw[1]))
        self.threshold = float(threshold)
        self.channels = int(channels)
        # set by engine_from_checkpoint: loads a NEW checkpoint with this
        # engine's exact model identity/quantization for a weight rollout
        # (serve/rollout.py); raw-built engines swap via arrays directly
        self.bundle_loader = None
        # monotonic over the engine's lifetime and NEVER rewound by a
        # rollback — version numbers are cache-key material (serve/
        # cache.py), so a rejected candidate's number must not be reused
        # by the next candidate
        self._version_counter = 0
        self.cache = (
            SampleCache(host_cache_mb * 2**20) if host_cache_mb > 0 else None
        )
        self.stateful = bool(getattr(model, "is_stateful", False))
        # int8 weights-only serving (ops/quant.py): `params` is the
        # quantized tree; each replica's device-resident weights stay one
        # byte per element and the forward dequantizes in-trace
        self.quantized = bool(quantized)
        # kernel policy (--kernels, ops/kernels.py): with serve_mask
        # engaged the AOT bucket executables threshold ON DEVICE through
        # the fused sigmoid/threshold kernel and return uint8 masks —
        # postprocess() then passes them through untouched (bit-identical
        # to the host threshold at the same operating point)
        self.kernel_policy = get_kernel_policy(kernels)
        self.mask_on_device = self.kernel_policy.serve_mask
        self._fwd = make_forward(
            model,
            quantized=self.quantized,
            mask_threshold=self.threshold if self.mask_on_device else None,
        )
        variables = bundle_variables(model, params, model_state)

        # content-addressed AOT executable store (utils/aotstore.py):
        # on hit each bucket executable LOADS instead of compiling; a
        # raw-built engine without a model fingerprint disables the
        # store — a key missing the model identity could load a
        # wrong program (engine_from_checkpoint always computes one)
        self.fingerprint = engine_fingerprint
        self.aot_store = AOTStore.resolve(aot_cache)
        if self.aot_store is not None and not self.fingerprint:
            logger.warning(
                "AOT executable store at %s DISABLED for this engine: "
                "no engine fingerprint (pass engine_fingerprint=... for "
                "raw-built engines)", self.aot_store.root,
            )
            self.aot_store = None
        # lifetime _compile_bucket invocations — the compile-count spy
        # seam (tests) and the rollout path's zero-recompile accounting
        self.aot_compiles = 0

        devices = jax.devices()
        n = max(1, min(int(replicas), len(devices)))
        if replicas > len(devices):
            logger.warning(
                "requested %d replicas but only %d devices — serving with %d",
                replicas, len(devices), n,
            )
        t0 = time.monotonic()
        self.replicas: List[Replica] = [
            self._build_replica(i, devices[i], variables) for i in range(n)
        ]
        loaded = self.aot_store.stats["hit"] if self.aot_store else 0
        logger.info(
            "AOT-compiled %d + store-loaded %d bucket executables (%s) "
            "x %d replica(s) in %.1f s — first-request latency pays "
            "no JIT",
            self.aot_compiles, loaded, list(self.planner.sizes), n,
            time.monotonic() - t0,
        )

    @classmethod
    def from_bundle(cls, bundle: InferenceBundle, **kwargs) -> "ServeEngine":
        kwargs.setdefault("quantized", bundle.quantized)
        return cls(
            bundle.model, bundle.params, bundle.model_state,
            input_hw=bundle.input_hw, **kwargs,
        )

    def _build_replica(self, index: int, device, variables) -> Replica:
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(device)
        vars_dev = jax.device_put(variables, sharding)
        h, w = self.input_hw
        jitted = serve_jit(self._fwd)
        compiled: Dict[int, object] = {}
        for b in self.planner.sizes:
            x_sds = jax.ShapeDtypeStruct(
                (b, h, w, self.channels), jnp.float32, sharding=sharding
            )
            key = meta = exe = None
            if self.aot_store is not None:
                key, meta = self._entry_key(b, device)
                exe = self.aot_store.load(key, meta, device)
            if exe is None:
                exe = self._compile_bucket(jitted, vars_dev, x_sds)
                if self.aot_store is not None:
                    self.aot_store.save(key, meta, exe)
            compiled[b] = exe
        return Replica(
            index=index, device=device, sharding=sharding,
            variables=vars_dev, compiled=compiled,
        )

    def _compile_bucket(self, jitted, vars_dev, x_sds):
        """The engine's ONLY compile site — store hits never reach it,
        which is what the compile-count spy tests pin."""
        self.aot_compiles += 1
        if self.aot_store is None:
            return jitted.lower(vars_dev, x_sds).compile()
        # The result is about to be persisted to the AOT store — and an
        # executable rehydrated from the persistent XLA compilation
        # cache serializes WITHOUT its backend kernel symbols, so the
        # store entry would be refused ("Symbols not found") by every
        # sibling process that tries to load it. Codegen fresh: the AOT
        # store replaces exactly what the XLA cache would have saved.
        # (no_xla_compilation_cache also resets jax's memoized
        # is-cache-used state — a bare flag flip is silently ignored
        # after the process's first compile.)
        from distributedpytorch_tpu.utils.aotstore import (
            no_xla_compilation_cache,
        )

        with no_xla_compilation_cache():
            return jitted.lower(vars_dev, x_sds).compile()

    def _entry_key(self, bucket: int, device) -> Tuple[str, dict]:
        """Store key for one bucket executable on one device. The
        on-device mask threshold is key material (it is baked into the
        trace); the device is too — each executable carries a
        ``SingleDeviceSharding`` and deserializes pinned to it. The
        device component goes through ``device_key`` so
        ``$DPT_AOT_KEY_SCHEME=kind`` can relax the full decorated
        string to a kind+ordinal scheme that identical chips share."""
        from distributedpytorch_tpu.utils.aotstore import (
            device_key,
            entry_key,
        )

        h, w = self.input_hw
        return entry_key(
            self.fingerprint,
            bucket,
            (bucket, h, w, self.channels),
            "float32",
            kernels=self.kernel_policy.name,
            mask_threshold=(
                self.threshold if self.mask_on_device else None
            ),
            quantized=self.quantized,
            stateful=self.stateful,
            device=device_key(device),
        )

    @property
    def aot_cache_stats(self) -> dict:
        """The store's cold-start story for THIS engine build (the
        serve ``/stats`` ``aot_cache`` block; the process-wide view is
        the ``dpt_aot_cache_total`` counter family)."""
        base = {"enabled": False, "dir": None,
                "hit": 0, "miss": 0, "skew": 0}
        if self.aot_store is not None:
            base.update({"enabled": True, "dir": self.aot_store.root,
                         **self.aot_store.stats})
        base["compiles"] = self.aot_compiles
        return base

    @property
    def num_replicas(self) -> int:
        return len(self.replicas)

    # -- live replica-group scaling (serve/scaler.py drives this) ------------
    def add_replica(self) -> Replica:
        """Grow the replica group by one device — the autoscaler's grow
        actuator. The new replica's weights come from replica 0's
        device-resident tree (the host tree is not retained past
        ``__init__``; a cross-device ``device_put`` re-homes it), so it
        joins at the currently promoted version. With a warm AOT store
        every bucket executable is a load, not a compile — which is
        what makes in-process scale-up cheap enough to actuate."""
        import jax

        devices = jax.devices()
        if self.num_replicas >= len(devices):
            raise RuntimeError(
                f"cannot grow past {len(devices)} device(s) "
                f"(already at {self.num_replicas} replicas)"
            )
        src = self.replicas[0]
        index = self.num_replicas
        replica = self._build_replica(index, devices[index], src.variables)
        replica.weights_version = src.weights_version
        self.replicas.append(replica)
        return replica

    def retire_replica(self) -> Replica:
        """Shrink the replica group by one — pops the highest-index
        replica. The caller (``Server.resize_replicas``) must have
        drained that replica's dispatch slots first; the device tree
        and executables are simply dropped (executables stay in the AOT
        store, so the next grow re-loads them)."""
        if self.num_replicas <= 1:
            raise RuntimeError("cannot retire the last replica")
        return self.replicas.pop()

    # -- zero-downtime weight hot-swap (serve/rollout.py drives this) --------
    @property
    def weights_version(self) -> int:
        """The version serving on EVERY replica group — what ``/stats``
        reports. During a canary the groups diverge; the promoted
        version is the fleet-wide floor."""
        return min(r.weights_version for r in self.replicas)

    @property
    def versions_mixed(self) -> bool:
        """True while replica groups serve different weight versions (a
        rollout canary is in flight) — the prediction cache bypasses
        itself then, since one key would map to two answers."""
        versions = {r.weights_version for r in self.replicas}
        return len(versions) > 1

    def next_weights_version(self) -> int:
        """A fresh, never-reused version number for a rollout candidate
        (rollbacks rewind replica versions, never this counter)."""
        return self._version_counter + 1

    def swap_weights(self, params, model_state=None, version: int = 0,
                     replica_indices: Optional[Sequence[int]] = None) -> None:
        """``device_put`` a new weight tree into the running replicas —
        no recompile, no drain: the AOT executables take ``variables`` as
        an *argument*, so the next dispatch simply passes the new tree
        (an in-flight dispatch keeps its old reference — the swap is a
        host-side pointer flip, atomic per replica).

        ``params`` must match the engine's compiled tree structure: a
        float engine takes float params, an int8 engine takes a
        quantized tree (``bundle_loader`` enforces this for checkpoint
        sources). The ``swap_crash`` chaos site fires per replica BEFORE
        its assignment, so an injected crash leaves that replica — and
        every later one — still serving the old weights."""
        import jax

        from distributedpytorch_tpu.utils import faults

        variables = bundle_variables(self.model, params, model_state)
        indices = (list(range(self.num_replicas))
                   if replica_indices is None else list(replica_indices))
        self._version_counter = max(self._version_counter, int(version))
        for i in indices:
            replica = self.replicas[i]
            if faults.fire("swap_crash", step=i):
                raise faults.InjectedFault(
                    f"injected swap_crash at replica {i}"
                )
            vars_dev = jax.device_put(variables, replica.sharding)
            # version BEFORE variables, matching the dispatch loop's
            # variables-then-version read order: the racing pair can
            # then read (old vars, new version) — a skipped cache put —
            # but never (new vars, old version), which would cache a
            # candidate's mask under the promoted version's key
            replica.weights_version = int(version)
            replica.variables = vars_dev

    def clone_weights(self, src_index: int,
                      dst_indices: Sequence[int]) -> None:
        """Copy one replica's device-resident weights (and version) onto
        other replicas — a device-to-device ``device_put``, no disk, no
        recompile. Same version-before-variables write order as
        ``swap_weights``. The sustained-A/B stop path promotes the
        winning arm's weights fleet-wide through this."""
        import jax

        src = self.replicas[int(src_index)]
        for i in dst_indices:
            replica = self.replicas[i]
            if replica is src:
                continue
            vars_dev = jax.device_put(src.variables, replica.sharding)
            replica.weights_version = src.weights_version
            replica.variables = vars_dev

    def restore_weights(self, saved: Dict[int, tuple]) -> None:
        """Roll back replicas to snapshots taken by
        :meth:`snapshot_weights` (the canary-rollback path — the old
        device trees were never freed, so this is another pointer flip).
        Same version-before-variables write order as ``swap_weights``;
        the version counter never rewinds."""
        for i, (variables, version) in saved.items():
            replica = self.replicas[i]
            replica.weights_version = version
            replica.variables = variables

    def snapshot_weights(
        self, replica_indices: Optional[Sequence[int]] = None
    ) -> Dict[int, tuple]:
        indices = (list(range(self.num_replicas))
                   if replica_indices is None else list(replica_indices))
        return {
            i: (self.replicas[i].variables, self.replicas[i].weights_version)
            for i in indices
        }

    # -- request path pieces (the server wires these together) ---------------
    def place(self, replica: Replica, batch: np.ndarray):
        """Host batch → replica's device. Non-blocking on async runtimes;
        the server runs it on the placement worker (pipelined_placement)
        so the H2D of bucket N+1 rides under bucket N's dispatch."""
        import jax

        return jax.device_put(batch, replica.sharding)

    def run(self, replica: Replica, x_dev):
        """Dispatch the bucket's compiled executable. Raises KeyError for
        a batch shape no executable was built for — bucket accounting
        bugs fail loudly instead of recompiling silently."""
        return replica.compiled[x_dev.shape[0]](replica.variables, x_dev)

    def infer(self, batch: np.ndarray, replica_index: int = 0) -> np.ndarray:
        """Synchronous single-bucket inference (tests, warmup): pads to
        the smallest covering bucket, runs, returns the REAL rows'
        probabilities as host float32 ``(n, H, W)`` — or, with the
        serve-mask kernel engaged, the ``(n, H, W) uint8`` masks the
        executable thresholded on device."""
        from distributedpytorch_tpu.serve.bucketing import pad_batch

        n = batch.shape[0]
        bucket = self.planner.bucket_for(n)
        if bucket is None:
            raise ValueError(
                f"batch of {n} exceeds the largest bucket "
                f"({self.planner.max_size})"
            )
        replica = self.replicas[replica_index]
        x = self.place(replica, pad_batch(np.asarray(batch, np.float32), bucket))
        return np.asarray(self.run(replica, x))[:n]

    def warmup(self) -> None:
        """Execute every (replica, bucket) once on zeros: allocator pools
        and any lazy runtime setup warm before traffic (compiles already
        happened at construction)."""
        h, w = self.input_hw
        for replica in self.replicas:
            for b in self.planner.sizes:
                x = self.place(
                    replica, np.zeros((b, h, w, self.channels), np.float32)
                )
                np.asarray(self.run(replica, x))

    # -- host-side decode (ingress; SampleCache-backed) ----------------------
    def preprocess(self, source, cache_key=None) -> np.ndarray:
        """One image source → a model input row ``(H, W, C) float32``.
        ``source`` may be a ready array (validated), a PIL image, or a
        path (decoded through the cache when one is configured —
        ``cache_key`` defaults to the path)."""
        h, w = self.input_hw
        if isinstance(source, np.ndarray):
            if source.shape != (h, w, self.channels):
                raise ValueError(
                    f"expected ({h}, {w}, {self.channels}) input row, got "
                    f"{source.shape}"
                )
            return np.asarray(source, np.float32)
        if isinstance(source, str):
            key = cache_key if cache_key is not None else (source, (w, h))
            if self.cache is not None:
                hit = self.cache.get(key)
                if hit is not None:
                    return hit["image"]
            from distributedpytorch_tpu.serve.infer import load_image

            row = load_image(source, (w, h))
            if self.cache is not None:
                self.cache.put(key, {"image": row})
            return row
        # PIL image (duck-typed: anything with .convert/.resize)
        return preprocess_image(source, (w, h))

    def postprocess(self, probs: np.ndarray) -> np.ndarray:
        return postprocess_mask(probs, self.threshold)


def engine_from_checkpoint(
    checkpoint: str,
    checkpoint_dir: str = "./checkpoints",
    image_size: Sequence[int] = (960, 640),
    model_arch: str = "unet",
    model_widths: Optional[Sequence[int]] = None,
    s2d_levels: int = -1,
    quantize: Optional[str] = None,
    **engine_kwargs,
) -> ServeEngine:
    """Checkpoint name/path → a ready (AOT-compiled) engine.
    ``quantize="int8"`` serves weights-only int8 (see
    serve/infer.load_inference_bundle for the file-vs-on-load rules)."""
    from distributedpytorch_tpu.obs.reqtrace import engine_fingerprint
    from distributedpytorch_tpu.serve.infer import load_inference_bundle

    # checkpoint-built engines always carry their model fingerprint —
    # the AOT store key material (and what bench_serve profiles stamp);
    # a caller-supplied one (tests faking skew) wins
    kernels = engine_kwargs.get("kernels", "xla")
    engine_kwargs.setdefault("engine_fingerprint", engine_fingerprint(
        model_arch=model_arch,
        image_size=image_size,
        model_widths=model_widths,
        s2d_levels=s2d_levels,
        quantize=quantize,
        kernels=getattr(kernels, "name", None) or str(kernels),
    ))
    bundle = load_inference_bundle(
        checkpoint, checkpoint_dir=checkpoint_dir, image_size=image_size,
        model_arch=model_arch, model_widths=model_widths,
        s2d_levels=s2d_levels, quantize=quantize,
    )
    engine = ServeEngine.from_bundle(bundle, **engine_kwargs)

    def _load_for_swap(new_checkpoint: str):
        """Load a rollout candidate with THIS engine's model identity and
        quantization (a float engine must not be handed an int8 tree —
        the compiled executables' argument structure would mismatch)."""
        new = load_inference_bundle(
            new_checkpoint, checkpoint_dir=checkpoint_dir,
            image_size=image_size, model_arch=model_arch,
            model_widths=model_widths, s2d_levels=s2d_levels,
            quantize="int8" if engine.quantized else None,
        )
        if new.quantized != engine.quantized:
            raise ValueError(
                f"{new_checkpoint} is "
                f"{'int8' if new.quantized else 'float'} but the engine "
                f"serves {'int8' if engine.quantized else 'float'} "
                f"weights — a hot-swap cannot change the executable's "
                f"argument structure"
            )
        return new

    engine.bundle_loader = _load_for_swap
    return engine
