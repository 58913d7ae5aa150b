"""CaaS Manager (paper §3.1) adapted to TPU pools: the "container service" is
a compiled-artifact service.

  container image  == compiled XLA executable for (arch, shape, step kind,
                      strategy); building the image == lower+compile; the
                      image registry == the content-addressed compile cache.
  pod              == a dispatch group submitted to the pool in ONE bulk call
                      (the paper's bulk submission that keeps OVH low).

The manager traces env setup/teardown per pod (TPT per the paper) and task
exec windows (TTX), executes noop/sleep/callable tasks directly, and routes
``compute`` tasks through the CompiledArtifactCache onto the provider's
device slice.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

from repro.core.pod import Pod
from repro.core.provider import ProviderHandle
from repro.core.task import Task, TaskState
from repro.runtime.clock import get_clock
from repro.runtime.tracing import span


class ProviderDown(RuntimeError):
    pass


def exec_span(task: Task, provider: str):
    """A task's run on its provider: stamps ``exec_start``, spans
    ``hydra.exec.task`` (shared by the CaaS and pilot managers)."""
    kernel = (task.payload or {}).get("kernel") if task.kind == "kernel" else task.kind
    return span("exec.task", task.trace, "exec_start", uid=task.uid, provider=provider, kernel=kernel)


class Preempted(RuntimeError):
    """A task was killed mid-execution by an external actor (spot reclaim,
    HPC walltime kill, chaos injection).  The killer calls
    ``task.mark_failed(Preempted(...))`` on a RUNNING task; the executing
    manager notices the FAILED state when the work function returns and
    reports the failure exactly once through the normal completion hook, so
    the broker's retry machinery owns the recovery."""


class CompiledArtifactCache:
    """Content-addressed cache of compiled step functions (the "image registry")."""

    def __init__(self):
        self._cache: dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self.builds = 0
        self.hits = 0

    def get_or_build(self, key: tuple, build: Callable[[], Any]):
        with self._lock:
            if key in self._cache:
                self.hits += 1
                return self._cache[key]
        artifact = build()  # compile outside the lock; duplicate builds are benign
        with self._lock:
            if key not in self._cache:
                self._cache[key] = artifact
                self.builds += 1
            return self._cache[key]


# Shared across managers: images are provider-agnostic, like a registry.
ARTIFACTS = CompiledArtifactCache()


class ComputeRuntime:
    """Executes ``compute`` tasks: builds/fetches the compiled step and runs a
    reduced-config instance on the device the provider hands it.  Programs
    and model state are kept per device."""

    def __init__(self):
        self._states: dict[tuple, Any] = {}
        self._lock = threading.Lock()

    def run(self, task: Task, device) -> Any:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from repro.configs import get_arch
        from repro.data.pipeline import DataConfig, batch_at
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.train import step as step_lib
        from repro.parallel.sharding import STRATEGIES

        arch = get_arch(task.arch).reduced()
        step_kind = task.step_kind or "train"
        key = (task.arch, step_kind, device)

        def build():
            model = Model(arch)
            mesh = Mesh(np.array([device]), ("data",))
            strategy = STRATEGIES["tp"]
            if step_kind == "train":
                fn = jax.jit(
                    step_lib.make_train_step(model, strategy, mesh, adamw.AdamWConfig())
                )
            elif step_kind == "prefill":
                fn = jax.jit(step_lib.make_prefill_step(model, strategy, mesh, cache_len=32))
            else:
                raise ValueError(step_kind)
            return model, fn

        model, fn = ARTIFACTS.get_or_build(key, build)
        dc = DataConfig(
            vocab_size=arch.vocab_size, seq_len=16, global_batch=2,
            enc_len=arch.enc_len_train, d_model=arch.d_model,
            n_img_tokens=arch.n_img_tokens, family=arch.family,
        )
        batch = jax.device_put(batch_at(dc, task.retries), device)
        with self._lock:
            state = self._states.get(key)
            if state is None:
                state = jax.device_put(
                    step_lib.init_train_state(model, jax.random.key(0)), device
                )
                self._states[key] = state
        placed = {"device": device.id, "platform": device.platform}
        if step_kind == "train":
            params, opt, metrics = fn(state[0], state[1], batch)
            with self._lock:
                self._states[key] = (params, opt)
            return {**{k: float(v) for k, v in metrics.items()}, **placed}
        logits, _ = fn(state[0], {k: v for k, v in batch.items() if k != "labels"})
        return {"logits_shape": list(logits.shape), **placed}


COMPUTE_RUNTIME = ComputeRuntime()


class KernelRuntime:
    """Executes ``kernel`` tasks: real Pallas work on the wire.

    ``task.payload`` is a plain dict::

        {"kernel": "rglru_scan",            # kernels/registry.py name
         "shape": {"B": 1, "L": 64, ...},   # omitted -> the kernel's tiny shape
         "dtype": "float32", "reps": 3, "seed": 0,
         "config": {"block_d": 512}}        # optional explicit blocks

    Block-config resolution mirrors kernels/ops.py: explicit payload config
    > autotuned cache (``HYDRA_AUTOTUNE=1`` only) > the kernel's committed
    defaults.  Operands are built on ``device`` from the seed and the kernel
    runs there as one compiled program (kernels/registry.py ``compiled``).
    Execution is rep-granular and resumable: ``progress_frac`` advances
    after every completed repetition, so a preempt-killed task that the
    checkpointer resumes (ckpt/checkpoint.py) skips the reps it already
    finished — only the partial rep in flight is re-executed.  The result
    names the device and carries the output's checksum.
    """

    def run(self, task: Task, device) -> Any:
        import time as _time

        import jax

        from repro.kernels import registry as kreg
        from repro.kernels.autotune import tuned_config

        spec = dict(task.payload or {})
        kdef = kreg.get_kernel(spec["kernel"])
        shape = dict(spec.get("shape") or kdef.tiny_shape)
        dtype = spec.get("dtype", "float32")
        reps = max(1, int(spec.get("reps", 1)))
        seed = int(spec.get("seed", 0))
        config = spec.get("config") or tuned_config(kdef.name, shape, dtype) or kdef.defaults(shape, dtype)
        program = kreg.compiled(kdef, shape, dtype, config, device)
        with span("kernel.operands", uid=task.uid):
            args = kreg.operands(kdef, shape, dtype, seed, device)
        done = min(reps, int(round(task.progress_frac * reps)))
        out = None
        t0 = _time.perf_counter()
        for r in range(done, reps):
            # launch: the host issues the kernel; sync: the chip runs the
            # operand build and the kernel, behind whatever it already holds
            with span("kernel.launch", uid=task.uid, rep=r):
                out = program(*args)
            task.trace.add("launched")
            with span("kernel.sync", uid=task.uid, rep=r):
                jax.block_until_ready(out)
            task.trace.add("synced")
            # completed-rep boundary: durable progress the checkpointer can
            # capture without losing more than the rep in flight
            task.kernel_done_s += _time.perf_counter() - t0
            t0 = _time.perf_counter()
            task.progress_frac = (r + 1) / reps
        kernel_s = task.kernel_done_s
        with span("kernel.checksum", uid=task.uid):
            total = None if out is None else kreg.checksum(out)
        # lifetime totals (reps survive preempt/resume cycles): the broker
        # emits ONE kernel.exec per completed task, so execs reconcile with
        # completed-task counts and reps/seconds with total work performed
        task.kernel_stats = {
            "kernel": kdef.name,
            "reps": reps,
            "kernel_s": kernel_s,
            "config": kreg.config_sig(config),
        }
        return {
            "kernel": kdef.name,
            "sig": kreg.shape_sig(shape, dtype),
            "config": kreg.config_sig(config),
            "reps": reps,
            "skipped_reps": done,
            "kernel_s": kernel_s,
            "device": device.id,
            "platform": device.platform,
            "checksum": total,
        }


KERNEL_RUNTIME = KernelRuntime()


class CaaSManager:
    """One per cloud-like provider.  Bulk pod submission + tracing."""

    def __init__(
        self,
        handle: ProviderHandle,
        on_task_done: Optional[Callable] = None,
        on_task_skipped: Optional[Callable] = None,
        on_task_finishing: Optional[Callable] = None,
    ):
        self.handle = handle
        self.spec = handle.spec
        self.on_task_done = on_task_done
        self.on_task_skipped = on_task_skipped
        # runs BEFORE mark_done resolves the future: resolving enqueues
        # dependent tasks synchronously, so anything a dependent must be able
        # to observe (declared outputs in the staging registry) registers here
        self.on_task_finishing = on_task_finishing
        self._pool = ThreadPoolExecutor(
            max_workers=self.spec.concurrency, thread_name_prefix=f"caas-{handle.name}"
        )
        self._down = threading.Event()
        self._inflight: set = set()
        self._lock = threading.Lock()
        # health signal counters: consumed by provider-group breakers and
        # the group-aware metrics rows (broker.group_rows / benchmarks)
        self.completed = 0
        self.failed = 0

    # -- lifecycle -----------------------------------------------------
    def fail(self):
        """Simulate a provider outage (tests / fault-tolerance benchmarks)."""
        self._down.set()

    def recover(self):
        self._down.clear()

    def stats(self) -> dict:
        return {
            "provider": self.handle.name,
            "down": self.down,
            "completed": self.completed,
            "failed": self.failed,
        }

    @property
    def down(self) -> bool:
        return self._down.is_set()

    def shutdown(self, wait: bool = True):
        self._pool.shutdown(wait=wait, cancel_futures=not wait)

    # -- submission ----------------------------------------------------
    def submit_pods(self, pods: list[Pod]):
        """Bulk submission: one enqueue per pod (not per task)."""
        if self.down:
            raise ProviderDown(self.handle.name)
        if self.spec.submit_latency_s:
            get_clock().sleep(self.spec.submit_latency_s)  # modeled API round-trip
        futures = []
        for pod in pods:
            for t in pod.tasks:
                t.try_advance(TaskState.SUBMITTED)
                t.trace.add("submitted")
            futures.append(self._pool.submit(self._run_pod, pod))
        return futures

    # -- execution -----------------------------------------------------
    def _run_pod(self, pod: Pod):
        # an executor thread took the pod: every task in it has its slot
        with span("exec.pod", [t.trace for t in pod.tasks], "slot", pod=pod.uid, provider=self.handle.name):
            pod.trace.add("env_setup_start")
            if self.spec.env_setup_s:
                get_clock().sleep(self.spec.env_setup_s * (1 if pod.model != "scpp" else 1.0))
            pod.trace.add("env_setup_done")
            try:
                for t in pod.tasks:
                    if self.down:
                        # fail the remaining tasks so the broker re-binds them
                        for rest in pod.tasks:
                            if (
                                not rest.final
                                and rest.provider == self.handle.name
                                and rest.mark_failed(ProviderDown(self.handle.name))
                                and self.on_task_done
                            ):
                                self.on_task_done(rest, self.handle.name, failed=True)
                        return
                    self._run_task(t)
            finally:
                pod.trace.add("env_teardown_start")
                pod.trace.add("env_teardown_done")

    def _run_task(self, task: Task):
        # canceled, speculatively completed elsewhere, or re-bound away:
        # tell the broker so group load accounting releases the slot
        if task.final or not task.try_advance(TaskState.RUNNING):
            if self.on_task_skipped:
                self.on_task_skipped(task, self.handle.name)
            return
        try:
            with exec_span(task, self.handle.name):
                result = self._execute(task)
        except Exception as e:
            if task.mark_failed(e):
                with self._lock:
                    self.failed += 1
                if self.on_task_done:
                    self.on_task_done(task, self.handle.name, failed=True)
            return
        if task.tstate == TaskState.FAILED:
            # preempt-style kill landed while _execute was running (see
            # Preempted): report the failure exactly once so the broker
            # retries it — the success path below would swallow it,
            # stranding the task's future forever
            with self._lock:
                self.failed += 1
            if self.on_task_done:
                self.on_task_done(task, self.handle.name, failed=True)
            return
        # skip on duplicate completions (speculation / post-rebind finishes):
        # mark_done no-ops those, and the hook must not re-register outputs
        if self.on_task_finishing and not task.final:
            self.on_task_finishing(task, self.handle.name)
        task.mark_done(result)
        with self._lock:
            self.completed += 1
        if self.on_task_done:
            self.on_task_done(task, self.handle.name, failed=False)

    def _execute(self, task: Task) -> Any:
        if task.kind == "noop":
            return None
        if task.kind == "sleep":
            # checkpoint resume (ckpt/checkpoint.py): only the work beyond
            # the captured progress_frac is re-executed
            get_clock().sleep(task.duration * (1.0 - task.progress_frac))
            return None
        if task.kind == "callable":
            return task.fn() if task.fn else None
        if task.kind == "compute":
            return COMPUTE_RUNTIME.run(task, self.handle.next_device())
        if task.kind == "kernel":
            return KERNEL_RUNTIME.run(task, self.handle.next_device())
        raise ValueError(task.kind)
