"""Test harness config: 8 virtual CPU devices, per SURVEY.md §4.

The reference validates its whole multi-node story without real accelerators
(envtest + gloo-on-kind); our analog is JAX's CPU backend with
``xla_force_host_platform_device_count=8`` giving a faked 8-device mesh in
one process. MUST run before the first ``import jax`` anywhere.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# compcache's default is a directory inside the checkout; the suite (and
# every subprocess it spawns) caches outside the tree so the checkout the
# driver copies does not grow with every run. Fixed path: the path is
# part of the cache key.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "kubeflow_tpu-test-jax-cache"),
)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


def wait_for_job_step(cluster, uid, step, timeout=240):
    """Poll worker-0 stdout until ``step=N`` appears (any attempt) —
    shared by the elastic and autoscaler e2e tests."""
    import time as _time

    from kubeflow_tpu.train.metrics import parse_stdout_metrics

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        if any(
            m["step"] >= step
            for m in parse_stdout_metrics(cluster.logs(uid, "worker", 0))
        ):
            return
        if cluster.status(uid).finished:
            raise AssertionError(
                f"job finished before reaching step {step}:\n"
                + cluster.logs(uid, "worker", 0)
            )
        _time.sleep(0.2)
    raise TimeoutError(
        f"step {step} not reached; log:\n" + cluster.logs(uid, "worker", 0)
    )


class GenerateOracle:
    """``serve/generate.py::make_generate_fn`` on the given weights, batch 1,
    greedy: the same-numerics reference every engine stream is pinned to.
    ``submit`` reads like the engine's, so a parity test states one
    prompt and one budget for both sides. One jit per budget."""

    def __init__(self, model, cfg, params, *, eos_id):
        self.model, self.cfg, self.params, self.eos_id = model, cfg, params, eos_id
        self._gens: dict = {}

    def submit(self, ids, max_new_tokens):
        import jax
        import numpy as np

        from kubeflow_tpu.serve.generate import make_generate_fn

        gen = self._gens.get(max_new_tokens)
        if gen is None:
            gen = self._gens[max_new_tokens] = jax.jit(make_generate_fn(
                self.model, self.cfg, max_new_tokens=max_new_tokens,
                eos_id=self.eos_id,
            ))
        prompt = np.zeros((1, -(-len(ids) // 32) * 32), np.int32)
        prompt[0, : len(ids)] = ids
        toks, n_valid = gen(
            self.params, prompt, np.asarray([len(ids)], np.int32),
            jax.random.PRNGKey(7), np.zeros((1,), np.float32),
        )
        return [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]


def drive_schedule(eng, schedule, *, cancel=None, max_iters=400):
    """Run an unstarted engine's scheduler on this thread, one
    ``_loop_once`` at a time, so that a test decides which iteration a
    request arrives in — and so what is in flight when it is admitted.
    ``schedule`` maps an iteration to the requests enqueued before it,
    each ``(ids, max_new_tokens, {"temperature": .., "seed": ..})`` (the
    dict optional); ``cancel`` maps an iteration to the indices, in
    arrival order, of requests cancelled before it. Iterations with
    nothing to run are skipped. Returns the requests in arrival order."""
    reqs, pending, it = [], None, 0
    cancel = cancel or {}
    last = max([*schedule, *cancel])
    while True:
        assert it < max_iters, "the schedule never drained"
        for ids, new, *kw in schedule.get(it, ()):
            kw = kw[0] if kw else {}
            reqs.append(eng._enqueue(
                list(ids), new, kw.get("temperature", 0.0), live=False,
                seed=kw.get("seed"),
            ))
        for i in cancel.get(it, ()):
            reqs[i].cancelled.set()
        it += 1
        if pending is None and not eng.busy():
            if it > last:
                break
            continue  # nothing resident: the next arrival is the work
        pending = eng._loop_once(pending)
    assert all(r.done.is_set() for r in reqs)
    return reqs
