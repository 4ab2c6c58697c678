"""Serving driver: batched prefill + decode loop with continuous batching.

A minimal production-shaped server: requests (prompt token lists) enter a
queue; the slot scheduler (launch/scheduling.py, shared with the SNN stream
server) packs up to `max_batch` active sequences; prefill runs per
admission; decode steps run the whole active batch through one jitted
decode_step (KV caches preallocated to max_seq).  Finished sequences free
their slots for queued requests (continuous batching).  Greedy or
temperature sampling.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 6 --max-new 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as make_reduced
from repro.launch import sharding as SH
from repro.launch.mesh import make_local_mesh
from repro.launch.scheduling import SlotScheduler
from repro.models import transformer as T

__all__ = ["Server", "Request"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    temperature: float = 0.0
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Server:
    def __init__(self, arch: str, use_reduced: bool = True,
                 max_batch: int = 4, max_seq: int = 512, seed: int = 0,
                 model_parallel: int = 1):
        self.cfg = make_reduced(get_config(arch)) if use_reduced \
            else get_config(arch)
        self.mesh = make_local_mesh(model_parallel)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self._rng = np.random.default_rng(seed)
        with SH.activate(self.mesh):
            self.params = T.init_params(self.cfg, jax.random.PRNGKey(seed))
            self._decode = jax.jit(
                lambda p, c, t: T.decode_step(p, self.cfg, c, t))
        self.sched = SlotScheduler(max_batch)
        self.finished: List[Request] = []
        self.caches = None
        self.slot_len: Dict[int, int] = {}

    # -- queue --------------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        return self.sched.queue

    @property
    def active(self) -> Dict[int, Request]:
        return self.sched.active

    def submit(self, req: Request) -> None:
        self.sched.submit(req)

    # -- internals ------------------------------------------------------------
    def _extra(self, b):
        extra = {}
        if self.cfg.family == "encdec":
            extra["audio"] = jnp.zeros((b, self.cfg.enc_seq,
                                        self.cfg.d_model), jnp.float32)
        if self.cfg.family == "vlm":
            extra["img"] = jnp.zeros((b, self.cfg.img_tokens,
                                      self.cfg.img_embed_dim), jnp.float32)
        return extra

    def _admit(self) -> None:
        """Prefill queued requests into free slots (one batch per admit)."""
        assigned = self.sched.admit()
        if not assigned:
            return
        slots = [s for s, _ in assigned]
        reqs = [r for _, r in assigned]
        take = len(reqs)
        maxlen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((take, maxlen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, maxlen - len(r.prompt):] = r.prompt  # left-pad
        with SH.activate(self.mesh):
            logits, caches = T.prefill(
                self.params, self.cfg, jnp.asarray(toks),
                self._extra(take), max_seq=self.max_seq)
        # merge these caches into the big batch (simple path: if no active
        # batch yet, adopt; otherwise run sequences independently per admit)
        if self.caches is None and take == self.max_batch:
            self.caches = caches
        for i, (r, s) in enumerate(zip(reqs, slots)):
            self.slot_len[s] = maxlen
            tok = self._sample(np.asarray(logits[i]), r)
            r.out.append(int(tok))
        # dedicated per-admit caches (slot-batched serving): store
        self._admit_caches = caches
        self._admit_slots = slots

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(np.argmax(logits))
        z = logits / req.temperature
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # -- main loop ------------------------------------------------------------
    def step(self) -> bool:
        """One decode step over the admitted batch; returns True if work
        remains."""
        if not self.active:
            self._admit()
            if not self.active:
                return False
        reqs = [self.active[s] for s in sorted(self.active)]
        last = jnp.asarray([r.out[-1] if r.out else r.prompt[-1]
                            for r in reqs], jnp.int32)
        with SH.activate(self.mesh):
            logits, self._admit_caches = self._decode(
                self.params, self._admit_caches, last)
        logits_np = np.asarray(logits)
        for i, (s, r) in enumerate(sorted(self.active.items())):
            tok = self._sample(logits_np[i], r)
            r.out.append(tok)
            if len(r.out) >= r.max_new:
                r.done = True
        for s in [s for s, r in self.active.items() if r.done]:
            self.finished.append(self.sched.release(s))
        if not self.active:
            self._admit_caches = None
            return bool(self.queue)
        return True

    def run(self) -> List[Request]:
        while self.step():
            pass
        return list(self.finished)

    def pop_finished(self) -> List[Request]:
        """Collect finished requests, pruning their accounting records so
        a long-lived server stays bounded (and their rids reusable)."""
        done, self.finished = self.finished, []
        for r in done:
            self.sched.forget(r.rid)
        return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    srv = Server(args.arch, use_reduced=not args.full,
                 max_batch=args.max_batch)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(3, srv.cfg.vocab,
                              size=rng.integers(4, 12)).tolist()
        r = Request(rid=i, prompt=prompt, max_new=args.max_new,
                    temperature=args.temperature)
        reqs.append(r)
        srv.submit(r)
    t0 = time.time()
    srv.run()
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in reqs)
    print(f"[serve] {args.requests} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
    print(f"[serve] latency: {srv.sched.latency_summary()}")
    for r in reqs[:4]:
        print(f"  req{r.rid}: prompt[:6]={r.prompt[:6]} -> out[:8]={r.out[:8]}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
