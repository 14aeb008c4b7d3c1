"""Serving launcher: batched prefill + decode with KV/state caches.

Drives the same ``prefill`` / ``decode_step`` entry points the dry-run lowers, with a
simple continuous-batching front: requests arrive with prompts, are batched, prefilled
once, then decoded step-locked. Greedy or temperature sampling.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --batch 4 --prompt-len 32 --gen-len 16

:class:`MicroBatchQueue` is the reusable continuous-batching front itself —
a thread-safe submit/drain queue that coalesces requests arriving within a
window into one batch for a caller-supplied batch processor. The token
server here and the placement service (:mod:`repro.deploy.service`) share it,
so it stays dependency-free (stdlib threading and :mod:`repro.obs` only; jax
imports below are deferred into the functions that need them).
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from ..obs import maybe_span


class MicroBatchQueue:
    """Coalesce concurrent submissions into micro-batches for one worker.

    ``process_batch`` is called from a single worker thread with a list of
    submitted items and must return one result per item, in order.
    :meth:`submit` blocks the calling thread until its item's result (or the
    batch's exception) is ready — the continuous-batching idiom: requests
    arriving within ``window_s`` of each other (up to ``max_batch``) share
    one processor dispatch. :meth:`submit_timed` also returns how long the
    item waited, from its submission to the hand-off of its batch to
    ``process_batch``; the batching window runs in a ``queue.window`` span.
    """

    _CLOSE = object()

    def __init__(self, process_batch, max_batch: int = 8,
                 window_s: float = 0.01):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self._process = process_batch
        self.max_batch = int(max_batch)
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._pending: list = []          # [(item, event, slot)]
        self._wake = threading.Event()
        self._closed = False
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def submit(self, item, timeout: float | None = None):
        """Enqueue ``item``; block until its result is ready and return it
        (re-raising the batch's exception if processing failed)."""
        return self.submit_timed(item, timeout)[0]

    def submit_timed(self, item, timeout: float | None = None):
        """:meth:`submit`, returning ``(result, wait_s)``: ``wait_s`` is the
        time from this call to the hand-off of the item's batch to
        ``process_batch``."""
        if self._closed:
            raise RuntimeError("queue is closed")
        done, slot = threading.Event(), {"t_submit": time.perf_counter()}
        with self._lock:
            self._pending.append((item, done, slot))
        self._wake.set()
        if not done.wait(timeout):
            raise TimeoutError(f"no result within {timeout}s")
        if "error" in slot:
            raise slot["error"]
        return slot["result"], slot["wait_s"]

    def close(self, timeout: float = 5.0) -> None:
        """Stop the worker after the current batch; pending items still run."""
        self._closed = True
        self._wake.set()
        self._worker.join(timeout)

    def _run(self) -> None:
        while True:
            self._wake.wait()
            with self._lock:
                if not self._pending:
                    if self._closed:
                        return
                    self._wake.clear()
                    continue
            # batching window: let near-simultaneous submissions pile up
            if self.window_s > 0:
                with maybe_span(None, "queue.window"):
                    deadline = time.perf_counter() + self.window_s
                    while time.perf_counter() < deadline:
                        with self._lock:
                            if len(self._pending) >= self.max_batch:
                                break
                        time.sleep(min(0.001, self.window_s))
            with self._lock:
                batch = self._pending[:self.max_batch]
                del self._pending[:self.max_batch]
                if not self._pending:
                    self._wake.clear()
                    if self._closed:
                        self._wake.set()   # drain remaining then exit
            items = [it for it, _, _ in batch]
            t_handoff = time.perf_counter()
            for _, _, slot in batch:
                slot["wait_s"] = t_handoff - slot["t_submit"]
            try:
                results = self._process(items)
                if len(results) != len(items):
                    raise RuntimeError(
                        f"process_batch returned {len(results)} results "
                        f"for {len(items)} items")
                for (_, done, slot), res in zip(batch, results):
                    slot["result"] = res
                    done.set()
            except Exception as e:  # noqa: BLE001 — propagate to submitters
                for _, done, slot in batch:
                    slot["error"] = e
                    done.set()


def generate(params, cfg, prompts, gen_len: int, max_len: int | None = None,
             temperature: float = 0.0, seed: int = 0):
    """prompts [B, P] int32 -> tokens [B, P+gen_len]. Greedy if temperature=0."""
    import jax
    import jax.numpy as jnp

    from ..models import lm
    from ..models.specs import materialize

    b, p = prompts.shape
    max_len = max_len or (p + gen_len)
    cache = materialize(jax.random.PRNGKey(0), lm.cache_specs(cfg, b, max_len))
    prefill_j = jax.jit(lambda pa, t, c: lm.prefill(pa, cfg, t, c))
    decode_j = jax.jit(lambda pa, c, t, i: lm.decode_step(pa, cfg, c, t, i),
                       donate_argnums=(1,))
    logits, cache = prefill_j(params, prompts, cache)
    key = jax.random.PRNGKey(seed)
    out = [prompts]
    tok = None
    for i in range(gen_len):
        if temperature > 0:
            key, k = jax.random.split(key)
            tok = jax.random.categorical(k, logits[:, -1] / temperature)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok.astype(jnp.int32))
        logits, cache = decode_j(params, cache, tok.astype(jnp.int32),
                                 jnp.int32(p + i))
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    import jax
    import jax.numpy as jnp

    from ..configs.registry import get_config, get_smoke_config
    from ..models import lm
    from ..models.encdec import EncDecConfig
    from ..models.specs import materialize

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if isinstance(cfg, EncDecConfig):
        raise SystemExit("use examples/seamless_serve for enc-dec serving")
    params = materialize(jax.random.PRNGKey(args.seed), lm.lm_specs(cfg))
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (args.batch, args.prompt_len)),
                          jnp.int32)
    t0 = time.time()
    toks = generate(params, cfg, prompts, args.gen_len,
                    temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    n_new = args.batch * args.gen_len
    print(f"generated {n_new} tokens in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s incl. prefill)")
    print("sample:", np.asarray(toks[0, -args.gen_len:]).tolist())
    return toks


if __name__ == "__main__":
    main()
