"""The replica a serving cell deploys: the program's ``LLMServer``,
serving the cell's weights (the program's init at the stream scale the
configuration states, handed over through the engine's own
``stage_weights``), with the probes only the process that
holds the chip can offer (when a request came in and when its first
token left, engine counters, compilations, memory, a profiler trace,
the correctness check). ``serve.run`` starts it in a worker pinned to
one chip."""
import time
from typing import Any, Dict, Optional, Sequence

from ray_tpu.serve.llm_engine import LLMServer


class BenchLLMServer(LLMServer):
    def __init__(self, model: Dict[str, Any], engine: Dict[str, Any],
                 seed: int, cell: str, rehearse: bool):
        t0 = time.time()
        super().__init__(model=model, engine=engine, seed=seed)
        from benchmarks import harness, spec
        self._cell = spec.load_cell(cell, rehearse)
        self._engine_kw = dict(engine)
        self._stage(harness.scale_stream(
            self.engine._params, self._cell.config["weights"], donate=True))
        self._compiles = harness.CompileCounter()
        self._heart = spec.Heartbeat()
        self._served = []
        self._tracer = None
        self._ready_s = time.time() - t0

    async def generate(self, prompt_ids: Sequence[int],
                       max_new_tokens: Optional[int] = None,
                       eos_token_id: Optional[int] = None):
        t_in, first = time.time(), None
        async for tok in super().generate(prompt_ids, max_new_tokens,
                                          eos_token_id):
            if first is None:
                first = time.time()
                self._served.append((t_in, first))
            yield tok

    def _stage(self, params) -> None:
        """Hand the engine its weights as a refresh does, and wait until
        its step thread has taken them (no request is in flight)."""
        if params is self.engine._params:
            return
        self.engine.stage_weights(params, version=1)
        deadline = time.time() + 60
        while self.engine._staged_weights is not None:
            if time.time() > deadline:
                raise RuntimeError("the engine did not take its weights")
            time.sleep(0.005)

    # ------------------------------------------------------------ probes
    def bench_facts(self) -> Dict[str, Any]:
        """Device, memory, compilations since the last reset, engine
        counters, and the (arrival, first token) wall-clock pairs of the
        requests served since the last call."""
        from benchmarks import harness
        served, self._served = self._served, []
        return {"device": self.device_info(),
                "memory": harness.memory_facts(),
                "compiles": self._compiles.n, "ready_s": self._ready_s,
                "heartbeat": list(self._heart.worst),
                "stats": self.engine.stats(), "served": served}

    def bench_reset(self) -> None:
        self._compiles.reset()
        self._heart.reset()
        self._served = []

    def bench_check(self, seed: int) -> Dict[str, Any]:
        from benchmarks import check
        return check.serve_check(self._cell, self.model_config,
                                 self.engine._params, self._engine_kw, seed)

    def bench_check_served(self, samples) -> Dict[str, Any]:
        from benchmarks import check
        return check.served_check(self._cell, self.engine._params, samples)

    def bench_trace_start(self) -> Dict[str, Any]:
        from benchmarks import harness
        self._tracer = harness.Tracer(harness.trace_dir(self._cell.name),
                                      self._cell.rehearse)
        self._tracer.start()
        return self.engine.stats()

    def bench_trace_stop(self) -> Dict[str, Any]:
        """Ends the traced stretch and returns the engine's counters at
        its end. The profiler runs on: stopping it and reducing what it
        wrote is tens of seconds of this process's time, which the cell
        asks for once its clients have stopped (``bench_trace_reduce``)."""
        stats = self.engine.stats()
        self._tracer.close()
        return stats

    def bench_trace_reduce(self) -> Dict[str, Any]:
        summary = self._tracer.finish()
        self._tracer = None
        return summary
