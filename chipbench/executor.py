"""The program's serving executor as the benchmark drives it.

:class:`BenchExecutor` is the program's ``JaxServeExecutor`` with three
changes, none of them on the device path: its weights are replaced by the
benchmark's seeded ones (made on the device in one jitted call, under the
program's own shardings, with the reference module's ``leaves``), its
prompts are drawn from (seed, request id),
and each call into it is recorded as a host span.
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

import jax
import numpy as np

from chipbench import weights


def prompt_ids(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7,
                                                        int(rid)]))
    return rng.integers(0, vocab, n, dtype=np.int64)


class Spans:
    """Host spans (name, start, end, info) on ``time.perf_counter``; with
    ``annotate`` each also goes into the profiler's trace."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: list = []

    @contextmanager
    def span(self, name: str, info=None):
        ann = jax.profiler.TraceAnnotation(name) if self.annotate \
            else nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter(), info))

    def of(self, name: str, t0: float, t1: float) -> list:
        """Spans called ``name`` that ended inside [t0, t1]."""
        return [r for r in self.rows if r[0] == name and t0 <= r[2] <= t1]


def bench_executor_class():
    """Built on first use: the program is imported only after the chip
    check, so a machine without one fails before touching it."""
    from repro.launch.serve import JaxServeExecutor, release

    class BenchExecutor(JaxServeExecutor):
        def __init__(self, plan, cfg, *, mesh, seed: int, spans: Spans,
                     leaves: dict, log=lambda what: None):
            super().__init__(plan, cfg, mesh=mesh)
            jax.block_until_ready((self.params, self.caches))
            log("the program's executor built")
            like = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self.params)
            shardings = jax.tree.map(lambda a: a.sharding, self.params)
            release(self.params)
            self.params = weights.program_params(seed, like, shardings,
                                                 cfg.vocab_size, leaves)
            self.seed = seed
            self.spans = spans

        def _prompt(self, req):
            return prompt_ids(self.seed, req.rid, req.prompt_len,
                              self.cfg.vocab_size)

        def prefill(self, states):
            with self.spans.span("executor.prefill",
                                 [st.req.prompt_len for st in states]):
                return super().prefill(states)

        def decode(self, states):
            # context per live row, counting the token this step reads
            with self.spans.span("executor.decode",
                                 [st.context_len for st in states]):
                return super().decode(states)

        def release(self):
            release((self.params, self.caches, self.last_logits))
            self.params = self.caches = self.last_logits = None

    return BenchExecutor
