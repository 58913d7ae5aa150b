"""Roofline-driven Pallas autotuner.

Sweeps the block/grid configs of every registered kernel per (device,
problem shape), prunes the sweep with the roofline cost model before any
candidate runs, times the survivors (warm-up + min-of-N), and caches each
winner as a pinned dataset in the staging registry its caller gives it
(``Hydra.enable_kernel_autotune`` passes the broker's registry and its
shared site) — so tuned configs flow through data-gravity placement and
survive site death exactly like any other artifact.

Pruning (the "provably dominated" rule)
---------------------------------------
Every admissible config computes the same result, so under the roofline
model ``t = max(flops/peak, hbm_bytes/bw) + grid_cells * launch_overhead``
a config A cannot beat a config B whose modeled FLOPs, HBM traffic, AND
grid-cell count are all <= A's (with at least one strictly smaller).  The
sweep therefore keeps only:

  1. configs whose VMEM tile footprint fits the per-core budget (16 MB on
     the v5e target), and
  2. the Pareto frontier of (flops, hbm_bytes, grid_cells) among those.

On the attention kernels this is a real three-way frontier (bigger blocks
=> fewer cell launches and less re-fetched K/V but more masked-out FLOPs);
on rglru the traffic is config-independent and the frontier collapses to
the single largest admissible block.

Cache keys and determinism
--------------------------
Winners key as ``tune:<kernel>:<device>:<shape-sig>`` where the shape sig
is the canonical sorted ``k=v`` string from kernels/registry.py.  The
cached dataset payload is canonical JSON of the *choice* (kernel, device,
shape, dtype, chosen config, sweep accounting, seed) — never the raw
timings — so identically-seeded runs produce byte-identical payloads and
the determinism test can compare them directly.  A cache hit returns the
stored result without re-timing and without emitting ``kernel.tune``.

Timers: ``timer="wall"`` (default) times the compiled program a kernel task
would run on the first device (interpreted off a TPU, Mosaic on one);
``timer="model"`` scores candidates purely with the roofline expression
above, priced at the published peaks of a TPU v5e — fully deterministic,
used by the determinism tests and the dry-run report's predicted-config
rows.

Nothing on the call path consults the tuner.  A tuned config reaches a
kernel the way any explicit config does: the caller puts
``TuneResult.config`` in a kernel task's payload ``config`` (or passes it
to ``ops.call``), and ``registry.block_config`` lets it win.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from repro.kernels import registry as kreg
from repro.roofline.model import V5E, peaks

# v5e per-core VMEM budget (see kernels/flash_attention.py footprint note)
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# modeled per-grid-cell launch overhead for the timer="model" roofline
# expression.  The absolute value only shifts the modeled times; what
# matters is that cell count is priced at all, so the model prefers fewer
# launches when FLOPs/traffic tie (which is also what the interpret path
# measures: its per-cell Python dispatch dominates at bench shapes).
MODEL_CELL_OVERHEAD_S = 1e-6

# the chip the modeled timer prices: the v5e the dry-run report models
MODEL_CHIP = peaks(V5E)

PAYLOAD_VERSION = 1


def device_kind() -> str:
    """The running chip's ``device_kind``, made a stable key fragment."""
    import jax

    return jax.devices()[0].device_kind.replace(" ", "-")


@dataclass
class TuneResult:
    kernel: str
    device: str
    sig: str
    key: str
    config: dict
    exhaustive: int  # full sweep-space size
    swept: int  # survivors actually timed
    pruned: int  # exhaustive - swept
    best_s: float  # winner's min-of-N (or modeled) seconds
    timings: dict = field(default_factory=dict)  # config sig -> seconds
    cached: bool = False  # True on cache hits (no re-timing happened)

    @property
    def sweep_cut(self) -> float:
        return self.exhaustive / self.swept if self.swept else float("inf")


class Autotuner:
    """Sweep, prune, time, cache.  One per broker (``Hydra.
    enable_kernel_autotune``) or standalone (benchmarks, dry run)."""

    def __init__(
        self,
        *,
        registry=None,  # staging DatasetRegistry (winners become datasets)
        site: Optional[str] = None,  # the registry site winners are pinned on
        events=None,  # EventBus (kernel.tune on cache misses)
        seed: int = 0,
        reps: int = 3,
        warmup: int = 1,
        timer: str = "wall",
        vmem_budget: int = VMEM_BUDGET_BYTES,
    ):
        assert timer in ("wall", "model"), timer
        assert registry is None or site is not None, "a registry needs the site to pin winners on"
        self.registry = registry
        self.site = site
        self.events = events
        self.seed = seed
        self.reps = reps
        self.warmup = warmup
        self.timer = timer
        self.vmem_budget = vmem_budget
        self._results: dict = {}  # cache key -> TuneResult
        self._payloads: dict = {}  # cache key -> bytes
        self._lock = threading.RLock()
        # legacy accumulators (HYDRA_EVENTS_CHECK ground truth, mirrored by
        # broker._events_recompute when this tuner is broker-attached)
        self.tunes = 0
        self.swept_configs = 0

    # -- keys ----------------------------------------------------------
    def cache_key(self, kernel: str, shape: dict, dtype: str, device: Optional[str] = None) -> str:
        device = device or device_kind()
        return f"tune:{kernel}:{device}:{kreg.shape_sig(shape, dtype)}"

    # -- pruning -------------------------------------------------------
    def prune(self, kernel: str, shape: dict, dtype: str = "float32"):
        """Returns ``(survivors, exhaustive_n)`` where survivors is the
        VMEM-admissible Pareto frontier of (flops, hbm_bytes, grid_cells),
        in sweep-space order (ties resolved deterministically downstream)."""
        kdef = kreg.get_kernel(kernel)
        space = kdef.space(shape)
        exhaustive = len(space)
        costed = [(cfg, kdef.cost(shape, cfg, dtype)) for cfg in space]
        fits = [(cfg, c) for cfg, c in costed if c.vmem_bytes <= self.vmem_budget]
        if not fits:
            # every candidate over budget (degenerate tiny-VMEM override):
            # fall back to the kernel defaults rather than an empty sweep
            return [kreg.block_config(kdef, shape, dtype)], exhaustive

        def dominated(ci: kreg.Cost) -> bool:
            for _, cj in fits:
                if cj is ci:
                    continue
                if (
                    cj.flops <= ci.flops
                    and cj.hbm_bytes <= ci.hbm_bytes
                    and cj.grid_cells <= ci.grid_cells
                    and (
                        cj.flops < ci.flops
                        or cj.hbm_bytes < ci.hbm_bytes
                        or cj.grid_cells < ci.grid_cells
                    )
                ):
                    return True
            return False

        survivors = [cfg for cfg, c in fits if not dominated(c)]
        return survivors, exhaustive

    # -- timing --------------------------------------------------------
    def _time_wall(self, thunk: Callable[[], object]) -> float:
        import jax

        for _ in range(self.warmup):
            jax.block_until_ready(thunk())
        best = float("inf")
        for _ in range(self.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(thunk())
            best = min(best, time.perf_counter() - t0)
        return best

    @staticmethod
    def model_time_s(cost: kreg.Cost) -> float:
        """Roofline-modeled seconds on a v5e: max(compute, memory) +
        launch tax."""
        return (
            max(cost.flops / MODEL_CHIP.flops, cost.hbm_bytes / MODEL_CHIP.hbm_bw)
            + cost.grid_cells * MODEL_CELL_OVERHEAD_S
        )

    # -- the sweep -----------------------------------------------------
    def tune(self, kernel: str, shape: dict, dtype: str = "float32") -> TuneResult:
        """Sweep (or cache-hit) the winning config for one problem.

        Coarse-grained lock: tuning is rare; holding the lock across the
        sweep keeps the cache-miss event count exact (one ``kernel.tune``
        per key)."""
        with self._lock:
            key = self.cache_key(kernel, shape, dtype)
            hit = self._results.get(key)
            if hit is not None:
                return TuneResult(**{**vars(hit), "cached": True})
            kdef = kreg.get_kernel(kernel)
            survivors, exhaustive = self.prune(kernel, shape, dtype)
            if self.timer == "wall":
                import jax

                device = jax.devices()[0]
                args = kreg.operands(kdef, shape, dtype, self.seed, device)
            best_cfg, best_s, timings = None, float("inf"), {}
            for cfg in survivors:
                if self.timer == "wall":
                    program = kreg.compiled(kdef, shape, dtype, cfg, device)
                    t = self._time_wall(lambda: program(*args))
                else:
                    t = self.model_time_s(kdef.cost(shape, cfg, dtype))
                timings[kreg.config_sig(cfg)] = t
                # strict < : ties keep the earlier (canonical-order) config,
                # so the choice is deterministic under the modeled timer
                if t < best_s:
                    best_cfg, best_s = cfg, t
            result = TuneResult(
                kernel=kernel,
                device=key.split(":")[2],
                sig=kreg.shape_sig(shape, dtype),
                key=key,
                config=dict(best_cfg),
                exhaustive=exhaustive,
                swept=len(survivors),
                pruned=exhaustive - len(survivors),
                best_s=best_s,
                timings=timings,
            )
            payload = self._payload_bytes(result, shape, dtype)
            self._results[key] = result
            self._payloads[key] = payload
            self._register_dataset(key, payload)
            self.tunes += 1
            self.swept_configs += result.swept
            if self.events is not None:
                self.events.emit(
                    "kernel.tune",
                    kernel=kernel,
                    sig=result.sig,
                    config=kreg.config_sig(result.config),
                    swept=result.swept,
                    exhaustive=exhaustive,
                )
            return result

    def _payload_bytes(self, result: TuneResult, shape: dict, dtype: str) -> bytes:
        # choice only, never timings: byte-identical across same-seed runs
        doc = {
            "version": PAYLOAD_VERSION,
            "kernel": result.kernel,
            "device": result.device,
            "dtype": dtype,
            "shape": {k: shape[k] for k in sorted(shape)},
            "sig": result.sig,
            "config": result.config,
            "exhaustive": result.exhaustive,
            "swept": result.swept,
            "pruned": result.pruned,
            "seed": self.seed,
            "reps": self.reps,
            "timer": self.timer,
        }
        return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()

    def _register_dataset(self, key: str, payload: bytes) -> None:
        if self.registry is None:
            return
        # pinned replica on the caller's site (the broker's shared store): a
        # tuned config is authoritative metadata, never LRU-evicted, and
        # survives any one site's death
        self.registry.add(
            key, size_mb=max(len(payload) / 1e6, 1e-6),
            sites=(self.site,), pinned=True,
        )

    # -- results -------------------------------------------------------
    def payload(self, key: str) -> Optional[bytes]:
        with self._lock:
            return self._payloads.get(key)

    def results(self) -> dict:
        with self._lock:
            return dict(self._results)

    def stats(self) -> dict:
        with self._lock:
            return {"tunes": self.tunes, "swept_configs": self.swept_configs}


def predict_best(kernel: str, shape: dict, dtype: str = "float32") -> dict:
    """Pure-model prediction (no execution): the config the roofline picks
    plus its predicted intensity — the dry-run report row that sits next to
    the HLO-derived intensity so predicted-vs-measured drift is visible."""
    tuner = Autotuner(timer="model")
    kdef = kreg.get_kernel(kernel)
    survivors, exhaustive = tuner.prune(kernel, shape, dtype)
    best_cfg, best_t = None, float("inf")
    for cfg in survivors:
        t = tuner.model_time_s(kdef.cost(shape, cfg, dtype))
        if t < best_t:
            best_cfg, best_t = cfg, t
    cost = kdef.cost(shape, best_cfg, dtype)
    return {
        "kernel": kernel,
        "sig": kreg.shape_sig(shape, dtype),
        "config": kreg.config_sig(best_cfg),
        "swept": len(survivors),
        "exhaustive": exhaustive,
        "intensity_flops_per_byte": round(cost.intensity, 3),
        "t_model_s": best_t,
    }


__all__ = [
    "VMEM_BUDGET_BYTES",
    "TuneResult",
    "Autotuner",
    "predict_best",
]
