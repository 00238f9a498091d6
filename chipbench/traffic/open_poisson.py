"""Open-loop serving: one child forked in set-up, one engine, requests
submitted as they fall due.

The engine admits one waiting request per step (its prefill) and decodes
every active one.  It holds at most ``max_active`` requests, active or
waiting, as a server's batch limit; a request due while it is full waits in
the harness's queue, and its latency counts the wait.  Its KV pool has
``max_active`` requests' worth of pages from the start, so the pool's shape
never changes in the window.
"""
from __future__ import annotations

import collections
import time

from chipbench.system import System, step_and_stamp
from chipbench.traffic.generate import warm_prompts

LATENCY = "ttft_ms_p95"


class Driver:
    def __init__(self, conf: dict, mix: dict, seed: int, spans, log):
        self.sys = System(conf, seed, spans, log)
        self.mix, self.seed, self.sp, self.log = mix, seed, spans, log
        self.model = self.sys.model
        self.tp = int(mix["kv_page_tokens"])
        self.max_active = int(mix["max_active"])
        longest = max(mix["prompt_tokens"]["values"]) + int(mix["output_tokens"])
        self.kv_frames = (self.max_active * 2 * self.model.cfg.num_layers
                          * -(-(longest + 1) // self.tp))

    def setup(self) -> None:
        """Seed the parent, warming every serving shape on its weights while
        they are on the device; then fork the child the window serves
        from."""
        self.sys.seed_parent(on_device=self._warm)
        self.child, self.params = self.sys.fork()
        self.eng = self._engine()

    def _engine(self):
        eng = self.sys.engine(self.params, kv_frames=self.kv_frames,
                              page_tokens=self.tp)
        sp = self.sp
        prefill, decode = eng._prefill, eng._decode_batch

        def timed_prefill(req):
            sp.samples["prefill_lengths"].append(len(req.prompt))
            with sp.span("prefill"):
                return prefill(req)

        def timed_decode(rids, key):
            sp.samples["decode_lengths"].append(
                [eng.kv.seqs[eng.requests[r].seq_id].length for r in rids])
            with sp.span("decode"):
                return decode(rids, key)

        eng._prefill, eng._decode_batch = timed_prefill, timed_decode
        return eng

    def _warm(self, weights) -> None:
        """Every (batch, pages) decode shape and every prefill length the
        window can draw, on an engine over ``weights``: per prompt length,
        one prefill, then forks of it (sharing its pages) so the batch
        climbs to ``max_active`` and drains again, all within the first
        decode page."""
        eng, n_max = self.sys.engine(weights, kv_frames=self.kv_frames,
                                     page_tokens=self.tp), self.max_active
        if n_max >= self.tp:
            raise ValueError("max_active must stay below kv_page_tokens")
        prompts = warm_prompts(self.mix, self.seed, self.model.dims.vocab)
        for n, prompt in prompts.items():
            with self.sp.span("warm"):
                r0 = eng.submit(prompt.tolist(), max_tokens=self.tp - 1)
                eng.step()
                for b in range(1, n_max):
                    eng.fork_request(r0, max_tokens=n_max - b + 1)
                    eng.step()
                while eng.active or eng.waiting:
                    eng.step()

    def window(self, sched, seconds: float, t0: float) -> list:
        eng, sp = self.eng, self.sp
        drain = seconds + float(self.mix["drain_s"])
        pending = collections.deque(sched)
        live = {}                       # engine request id -> record
        recs = []
        while pending or live:
            now = time.perf_counter() - t0
            if now > drain:
                break
            while (pending and pending[0].due <= now
                   and len(eng.active) + len(eng.waiting) < self.max_active):
                req = pending.popleft()
                rec = {"idx": req.idx, "due": req.due, "prompt": req.prompt,
                       "times": [], "tokens": [], "failed": False,
                       "max_tokens": req.max_tokens}
                recs.append(rec)
                live[eng.submit(req.prompt.tolist(),
                                max_tokens=req.max_tokens)] = rec
            if live:
                try:
                    with sp.span("step"):
                        step_and_stamp(eng, live, t0)
                except Exception as e:  # the requests in the engine fail
                    self.log(f"engine step failed: {e!r}")
                    for rec in live.values():
                        rec["failed"] = True
                    live.clear()
                    self.eng = eng = self._engine()
                    continue
                for rid in [r for r in live if eng.requests[r].done]:
                    live.pop(rid)["tokens"] = list(eng.requests[rid].out_tokens)
                    eng.requests.pop(rid)
            elif pending:
                with sp.span("wait"):
                    time.sleep(max(0.0, pending[0].due
                                   - (time.perf_counter() - t0)))
        for rec in list(live.values()) + [
                {"idx": r.idx, "due": r.due, "prompt": r.prompt, "times": [],
                 "tokens": [], "max_tokens": r.max_tokens} for r in pending]:
            rec["failed"] = True
            if rec not in recs:
                recs.append(rec)
        for rec in recs:
            if len(rec["tokens"]) != rec["max_tokens"]:
                rec["failed"] = True
        return recs

    def work(self) -> dict:
        ref, dm, s = self.model.ref, self.model.dims, self.sp.samples
        att = [ref.paged_attention_work(dm, lens, self.tp)
               for lens in s["decode_lengths"]]
        layers = self.model.cfg.num_layers
        return {"prefill_flops": sum(ref.prefill_flops(dm, n)
                                     for n in s["prefill_lengths"]),
                "decode_flops": sum(ref.decode_flops(dm, lens)
                                    for lens in s["decode_lengths"]),
                "paged_attention": (layers * sum(f for f, _ in att),
                                    layers * sum(b for _, b in att))}

    def release(self) -> None:
        self.eng = self.params = None
        self.child.free()
        self.child = None
        self.sys.release()
