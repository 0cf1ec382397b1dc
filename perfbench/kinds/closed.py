"""Traffic kind ``closed``: a fixed number of clients, each sending its
next request when the last one completes.  The window opens with the
batch full and the sessions at staggered depths."""
from __future__ import annotations

from pb import serve_common, serve_loop, traffic


def tiny(mix: dict, limits: dict) -> tuple:
    """A mix of this kind and its cell's limits at sizes a CPU test can
    hold (``tests/perfbench/pb_tiny.py``): float32 serving picks the
    reference's own token everywhere there."""
    mix = dict(mix, prompt={"dist": "uniform", "lo": 6, "hi": 40},
               output={"dist": "uniform", "lo": 4, "hi": 12},
               max_total=96, warm_prompt_lens=[9, 30],
               warm_prefill_lens=[], cycle=8)
    return mix, dict(limits, served_sq_gap_per_close_call=1e-9)


class _Source:
    """Requests in cycles of stratified lengths, drawn as needed."""

    def __init__(self, mix, seed, vocab):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.cycle = 0
        self.buf = []

    def next(self):
        if not self.buf:
            self.buf = traffic.serve_requests(
                self.mix, self.seed, self.vocab, self.mix["cycle"],
                cycle=self.cycle)[::-1]
            self.cycle += 1
        return self.buf.pop()


def run(cell, args, env, fault=None, eng=None):
    cfg, mix = cell.config, cell.traffic
    vocab = cell.family.vocab(cfg)
    max_batch = cfg["serve"]["max_batch"]
    clients = int(mix["clients_per_slot"] * max_batch)
    source = _Source(mix, args.seed, vocab)

    def lead_in(loop):
        """Fill the batch with sessions at staggered depths: the first
        ``max_batch`` requests start as if the share f = (i + 0.5) /
        max_batch of their output were already generated (those tokens
        are folded into the prompt), deepest first; the other clients
        queue behind them.  Runs until the batch is full."""
        rng = traffic.rng_for(args.seed, "stagger")
        first = [source.next() for _ in range(max_batch)]
        shares = (rng.permutation(max_batch) + 0.5) / max_batch
        folded = []
        for r, f in zip(first, shares):
            gen = int(f * r["max_new"])
            gen = min(gen, r["max_new"] - 1)
            folded.append({
                "rid": r["rid"],
                "prompt": r["prompt"] + traffic.token_ids(rng, gen, vocab),
                "max_new": r["max_new"] - gen})
        folded.sort(key=lambda r: -len(r["prompt"]))
        for r in folded:
            loop.submit(serve_loop.Tracked(r, True))
        for _ in range(clients - max_batch):
            loop.submit(serve_loop.Tracked(source.next(), True))
        ticks = 0
        while len(loop.eng.scheduler.decode_sessions()) < max_batch \
                and ticks < 8 * max_batch:
            for _ in loop.tick():
                loop.submit(serve_loop.Tracked(source.next(), True))
            ticks += 1

    def drive(loop, seconds, t0):
        tracked = list(loop.live.values())
        while env.now() - t0 < seconds:
            for _ in loop.tick():
                tr = serve_loop.Tracked(source.next(), True)
                loop.submit(tr)
                tracked.append(tr)
        return tracked, env.now()

    return serve_common.run(cell, args, env, lead_in, drive, fault, eng=eng)
