"""Fork per invocation (MITOSIS's mode without cached children, §6.2).

Each request is one invocation on the device-pool node: fork a fresh child
from the seed (``resume_on``, lazy), materialize it, serve the request's
prompt for its greedy tokens on a new engine, and free the child.  One node
serves invocations one after another; an invocation due while another runs
waits, and its latency counts the wait (open loop, timed from the due time).
"""
from __future__ import annotations

import time

import jax

from chipbench import work
from chipbench.system import System, leaf_sums, step_and_stamp
from chipbench.traffic.generate import warm_prompts

LATENCY = "invoke_ms_p50"


class Driver:
    def __init__(self, conf: dict, mix: dict, seed: int, spans, log):
        self.sys = System(conf, seed, spans, log)
        self.mix, self.seed, self.sp, self.log = mix, seed, spans, log
        self.model = self.sys.model
        self.sums = {}          # request idx -> per-leaf bit sums (device)

    def setup(self) -> None:
        """Seed the parent, warming the serving shapes on its weights while
        they are on the device; then one fork, materialize and check, which
        compiles the page kernels, and free the child."""
        self.sys.seed_parent(on_device=self._warm_serve)
        with self.sp.span("warm"):
            child, params = self.sys.fork()
            jax.block_until_ready(leaf_sums(params))
            child.free()
            del params

    def _warm_serve(self, weights) -> None:
        """One request of each prompt length the mix draws, each on an
        engine of its own, as the window serves them."""
        for prompt in warm_prompts(self.mix, self.seed,
                                   self.model.dims.vocab).values():
            eng = self.sys.engine(weights)
            eng.submit(prompt.tolist(), max_tokens=int(self.mix["output_tokens"]))
            while eng.waiting or eng.active:
                eng.step()

    def _invoke(self, prompt, max_tokens: int, rec: dict, t0: float) -> None:
        sp, meter = self.sp, self.sys.net.meter
        before = {k: meter[k] for k in ("pool.scatter_pages",
                                        "pool.assemble_pages")}
        with sp.span("invoke"):
            child, params = self.sys.fork()
            with sp.span("serve"):
                eng = self.sys.engine(params)
                rid = eng.submit(prompt.tolist(), max_tokens=max_tokens)
                while eng.waiting or eng.active:
                    step_and_stamp(eng, {rid: rec}, t0)
                rec["tokens"] = list(eng.requests[rid].out_tokens)
            with sp.span("check"):
                self.sums[rec["idx"]] = jax.block_until_ready(
                    leaf_sums(params))
            with sp.span("free"):
                child.free()
                del eng, params
        for k, v in before.items():
            self.sp.counters[k] += meter[k] - v
        self.sp.counters["invocations"] += 1
        self.sp.samples["prompt_lengths"].append(len(prompt))

    def window(self, sched, seconds: float, t0: float) -> list:
        """Serve ``sched`` from ``t0``; returns a record per request."""
        recs = []
        drain = seconds + float(self.mix["drain_s"])
        for req in sched:
            rec = {"idx": req.idx, "due": req.due, "prompt": req.prompt,
                   "times": [], "tokens": [], "failed": False}
            recs.append(rec)
            wait = req.due - (time.perf_counter() - t0)
            if wait > 0:
                with self.sp.span("wait"):
                    time.sleep(wait)
            if time.perf_counter() - t0 > drain:
                rec["failed"] = True
                continue
            try:
                self._invoke(req.prompt, req.max_tokens, rec, t0)
            except Exception as e:      # a failed invocation, counted
                self.log(f"invocation {req.idx} failed: {e!r}")
                rec["failed"] = True
            if len(rec["tokens"]) != req.max_tokens:
                rec["failed"] = True
        return recs

    def work(self) -> dict:
        """Required work of what the window ran, for the metric readers."""
        c, pb = self.sp.counters, self.sys.page_bytes
        ref, dm = self.model.ref, self.model.dims
        lens = self.sp.samples["prompt_lengths"]
        new = int(self.mix["output_tokens"]) - 1
        flops = sum(ref.prefill_flops(dm, n)
                    + sum(ref.decode_flops(dm, [n + i]) for i in range(new))
                    for n in lens)
        return {"page_gather": (0, work.page_move_bytes(
                    c["pool.assemble_pages"], pb)),
                "cow_scatter": (0, work.page_move_bytes(
                    c["pool.scatter_pages"], pb)),
                "invoke_flops": flops}

    def release(self) -> None:
        self.sys.release()
