"""Driving ``ServeEngine.submit`` / ``step`` from one thread.

The generator and the engine share the thread: every request that is
due is submitted before the next ``engine.step()``.  Token times are
the harness's clock at the return of the step that produced them.
"""
from __future__ import annotations

from . import sut

SERVE_KINDS = ("block_copy", "prefill_step", "decode_step")


def _kind_counts() -> dict:
    """``{kind: (dispatches, compiles)}`` from the program's step cache."""
    out = {}
    for k in SERVE_KINDS:
        st = sut.kind_stats(k)
        out[k] = (st["dispatches"], st["compiles"])
    return out


class Tracked:
    __slots__ = ("rid", "prompt", "max_new", "token_times", "sess",
                 "counted", "done")

    def __init__(self, req: dict, counted: bool):
        self.rid = req["rid"]
        self.prompt = req["prompt"]
        self.max_new = req["max_new"]
        self.counted = counted
        self.token_times = []
        self.sess = None
        self.done = False


class Loop:
    """One engine and the requests the harness has given it."""

    def __init__(self, eng, env):
        self.eng = eng
        self.env = env
        self.live = {}                  # rid -> Tracked (submitted, not done)
        self.finished = []              # Tracked, in completion order
        self.ticks = []                 # per tick: dict
        self.on_token = None            # test hook: fault injection

    def submit(self, tr: Tracked) -> None:
        self.eng.submit(sut.make_request(tr.rid, tr.prompt, tr.max_new))
        tr.sess = self.eng.scheduler.queue[-1]
        self.live[tr.rid] = tr

    def tick(self) -> list:
        """One ``engine.step()``; returns the requests it finished."""
        import jax
        before = _kind_counts()
        t0 = self.env.now()
        with jax.profiler.TraceAnnotation("engine.step"):
            self.eng.step()
        now = self.env.now()
        new_tokens = firsts = 0
        done = []
        for tr in list(self.live.values()):
            s = tr.sess
            if self.on_token is not None:
                self.on_token(tr, s)
            n = len(s.out)
            if n > len(tr.token_times):
                if not tr.token_times:
                    firsts += 1
                new_tokens += n - len(tr.token_times)
                tr.token_times.extend([now] * (n - len(tr.token_times)))
            if tr.rid in self.eng.results:
                tr.done = True
                del self.live[tr.rid]
                self.finished.append(tr)
                done.append(tr)
        after = _kind_counts()
        log = []
        for k in SERVE_KINDS:
            log += [k] * (after[k][0] - before[k][0])
        self.ticks.append({
            "t0": t0, "t1": now, "dispatches": log,
            "compiles": sum(after[k][1] - before[k][1]
                            for k in SERVE_KINDS),
            "new_tokens": new_tokens, "decode_batch": new_tokens - firsts,
            # KV rows the decode dispatch of this tick read: every
            # decoding session's depth (read after the tick)
            "kv_tokens": sum(s.position for s in
                             self.eng.scheduler.decode_sessions()),
        })
        return done

    def busy(self) -> bool:
        return bool(self.live)

    def served(self, tr: Tracked) -> list:
        """The tokens ``tr`` was served: its answer where it is
        finished, else as far as its session got."""
        if tr.rid in self.eng.results:
            return list(self.eng.results[tr.rid])
        return list(tr.sess.out)


def warm_waves(loop: Loop, mix: dict, vocab: int, max_batch: int,
               rng) -> int:
    """Warm the shapes the traffic reaches and no others.  Each of
    ``warm_prompt_lens`` is a wave of ``max_batch`` requests of that
    prompt length submitted at once, so that the decode batch ramps
    through every bucket at that wave's table bucket and its prefill
    program runs; each of ``warm_prefill_lens`` is one request of one
    new token, which runs the prefill program of its table bucket and
    no decode.  Returns the ticks used."""
    ticks = 0
    waves = [(plen, max_batch, max_batch + 8)
             for plen in mix["warm_prompt_lens"]] \
        + [(plen, 1, 1) for plen in mix.get("warm_prefill_lens", [])]
    for w, (plen, count, max_new) in enumerate(waves):
        for i in range(count):
            req = {"rid": f"warm{w}.{i}",
                   "prompt": [int(t) for t in rng.integers(1, vocab, plen)],
                   "max_new": max_new}
            loop.submit(Tracked(req, False))
        while loop.busy():
            loop.tick()
            ticks += 1
    loop.finished.clear()
    loop.ticks.clear()
    return ticks
