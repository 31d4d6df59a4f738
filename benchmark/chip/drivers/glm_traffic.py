"""The generator of the `shared_docs_zipf` mix (traffic/
shared_docs_zipf.json, kind `closed_loop`; traffic.py reads the file
and knows the kind, this file reads the keys the mix adds).

A pool of long documents, made from the seed; a request is one of them
and a new question behind it. Which document (Zipf over the pool, by
popularity rank), how long the question and how many new tokens are
drawn in blocks of `stratum` requests, each holding every choice in its
expected number, from the file's own `order_seed`: the sequence is the
same for every `--seed`, because which document is asked, at what
length, decides the work. The seed decides what documents and questions
say. The rank a document has is fixed too (documents differ in length).
"""
import numpy as np

from .. import traffic


def stratified(rng, shares, stratum, n_draws):
    """`n_draws` indices into `shares` (probabilities): in every block
    of `stratum` draws index i appears floor(stratum * p_i) times, the
    rest of the block is drawn from what the floors left over, and the
    block is shuffled (traffic.stratified_zipf, for any shares)."""
    p = np.asarray(shares, np.float64)
    p = p / p.sum()
    base = np.floor(stratum * p + 1e-9).astype(np.int64)
    rest = np.maximum(stratum * p - base, 0.0)
    n_rest = stratum - int(base.sum())
    fixed = np.repeat(np.arange(len(p)), base)
    blocks = []
    for _ in range(-(-n_draws // stratum)):
        extra = rng.choice(len(p), size=n_rest, p=rest / rest.sum()) \
            if n_rest else np.zeros((0,), np.int64)
        block = np.concatenate([fixed, extra])
        rng.shuffle(block)
        blocks.append(block)
    return np.concatenate(blocks)[:n_draws]


class SharedDocs:
    """The requests of the closed loop, in the order they are sent."""

    def __init__(self, seed, spec, sizes):
        self.callers = spec["callers"]
        self.max_requests = n = spec["max_requests"]
        self.lo, self.vocab = spec["id_low"], sizes["vocab"]
        rng = np.random.default_rng([int(seed), 2])
        lengths = [t for t, count in spec["documents"]
                   for _ in range(count)]
        self.docs = [rng.integers(self.lo, self.vocab, t, dtype=np.int64)
                     for t in lengths]

        def order(stream):
            return np.random.default_rng([spec["order_seed"], stream])

        ranks = traffic.stratified_zipf(
            order(4), len(self.docs), spec["zipf_s"], spec["stratum"], n)
        # popularity is not tied to a document's length
        self.doc_of = order(5).permutation(len(self.docs))[ranks]
        q_len, q_share = zip(*spec["question_tokens"])
        self.q_len = np.asarray(q_len)[stratified(
            order(6), q_share, spec["stratum"], n)]
        m_new, m_share = zip(*spec["max_new_tokens"])
        self.max_new = np.asarray(m_new)[stratified(
            order(7), m_share, spec["stratum"], n)]
        self.questions = np.random.default_rng([int(seed), 3])
        self.sent = 0

    def next_request(self):
        """(prompt, max_new_tokens, cache_tokens, document index)."""
        if self.sent >= self.max_requests:
            raise RuntimeError(
                f"traffic exhausted after {self.sent} requests; raise "
                f"max_requests in the traffic file")
        i, self.sent = self.sent, self.sent + 1
        doc = self.docs[self.doc_of[i]]
        question = self.questions.integers(
            self.lo, self.vocab, int(self.q_len[i]), dtype=np.int64)
        return (np.concatenate([doc, question]), int(self.max_new[i]),
                len(doc), int(self.doc_of[i]))
