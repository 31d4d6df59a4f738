"""The one general traffic generator. A traffic mix is a data file
under traffic/ (parameters only); this module turns it and a seed into
the inputs a driver sends. The same seed gives the same inputs; every
seed gives the same sizes and, for skewed draws, the same composition
in another order.

kinds:
  "train_batches"  a pool of `pool_batches` sentence-pair batches, used
                   one a step in order (the pool outlasts any window at
                   today's step time; it wraps if a faster program ever
                   outruns it). Each row is a random sequence of
                   seq_len + 1 ids from [id_low, vocab): the decoder
                   reads it shifted right behind the start token and is
                   scored against it, as a translation batch is.
  "closed_loop"    `callers` callers, each of which submits its next
                   prompt when its reply ends. `prompt_pool` = 0 makes
                   every prompt new; otherwise prompts are drawn from a
                   pool of that many by Zipf(`zipf_s`), stratified in
                   blocks of `stratum` draws so that every stretch of a
                   run holds the popular prompts in their expected
                   numbers. The sequence of popularity ranks comes from
                   the file's own `order_seed`, the same for every
                   `--seed`: which requests hit, miss or replay decides
                   the work, so it belongs to the mix; the seed decides
                   what the prompts say and which prompt has which
                   rank.
"""
import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    path = os.path.join(_HERE, "traffic", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") not in ("train_batches", "closed_loop"):
        raise ValueError(f"{path}: unknown traffic kind "
                         f"{spec.get('kind')!r}")
    return spec


def _rng(seed, stream):
    return np.random.default_rng([int(seed), stream])


def train_batches(seed, spec, sizes, start_id):
    """List of feeds {src_ids, tgt_ids, label}, each [batch, seq]."""
    rng = _rng(seed, 1)
    b, t, v = sizes["batch"], sizes["seq_len"], sizes["vocab"]
    lo = spec["id_low"]
    out = []
    for _ in range(spec["pool_batches"]):
        src = rng.integers(lo, v, (b, t), dtype=np.int64)
        seq = rng.integers(lo, v, (b, t + 1), dtype=np.int64)
        tgt = seq[:, :-1].copy()
        tgt[:, 0] = start_id
        out.append({"src_ids": src, "tgt_ids": tgt,
                    "label": np.ascontiguousarray(seq[:, 1:])})
    return out


def zipf_probabilities(n, s):
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def stratified_zipf(rng, n_pool, s, stratum, n_draws):
    """`n_draws` ranks in [0, n_pool): in every block of `stratum`
    draws each rank appears floor(stratum * p) times, the rest of the
    block is drawn from what the floors left over, and the block is
    shuffled."""
    p = zipf_probabilities(n_pool, s)
    base = np.floor(stratum * p).astype(np.int64)
    rest = stratum * p - base
    n_rest = stratum - int(base.sum())
    fixed = np.repeat(np.arange(n_pool), base)
    blocks = []
    for _ in range(-(-n_draws // stratum)):
        extra = rng.choice(n_pool, size=n_rest, p=rest / rest.sum())
        block = np.concatenate([fixed, extra])
        rng.shuffle(block)
        blocks.append(block)
    return np.concatenate(blocks)[:n_draws]


class ClosedLoop:
    """The prompts of a closed loop, in the order they are sent."""

    def __init__(self, seed, spec, sizes):
        self.callers = spec["callers"]
        self.max_requests = spec["max_requests"]
        t, v, lo = sizes["seq_len"], sizes["vocab"], spec["id_low"]
        rng = _rng(seed, 2)
        n_pool = spec["prompt_pool"]
        if n_pool:
            self.pool = rng.integers(lo, v, (n_pool, t), dtype=np.int64)
            ranks = stratified_zipf(
                _rng(spec["order_seed"], 4), n_pool, spec["zipf_s"],
                spec["stratum"], self.max_requests)
            # popularity is not tied to the pool's order
            self.rank_to_pool = rng.permutation(n_pool)
            self.order = self.rank_to_pool[ranks]
        else:
            self.pool = rng.integers(lo, v, (self.max_requests, t),
                                     dtype=np.int64)
            self.order = np.arange(self.max_requests)
        self.sent = 0

    def next_prompt(self):
        if self.sent >= self.max_requests:
            raise RuntimeError(
                f"traffic exhausted after {self.sent} requests; raise "
                f"max_requests in the traffic file")
        prompt = self.pool[self.order[self.sent]]
        self.sent += 1
        return prompt

    def by_popularity(self, n):
        """The `n` most popular prompts of a pooled mix (what a warm
        table holds), most popular first."""
        return [self.pool[i] for i in self.rank_to_pool[:n]]
