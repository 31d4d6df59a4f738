"""Property tests for the host allocator automaton
(models/decode_engine.HostBlockPool / PromptPrefixCache).

These classes are the HOST half of the pool-ownership bargain: the
ownership prover (PTA190/191/192, analysis/absint.py) proves device
programs lane-exclusive GIVEN the named invariants below, so the
invariants themselves must be machine-checked, not folklore:

* ``HostBlockPool.alloc-disjoint`` — a block is owned by exactly one
  lane between alloc and free: randomized alloc/free traces never
  yield overlapping live blocks, and bad lifetime transitions
  (double free, free of unallocated, out-of-range) raise the NAMED
  ``BlockLifetimeError`` instead of corrupting the free list;
* ``PromptPrefixCache.fresh-exclusive`` — a fresh entry starts at
  refcount 1 (the exclusive write window admission prefill uses);
  refcounts stay >= 0 (release below zero raises), shared entries
  (refcount > 1) are never ``writable``, and LRU eviction only ever
  touches UNPINNED entries (refcount == 0).

The FAST lane is exhaustive: analysis/protomodel.py explores every
reachable interleaving of each allocator machine at small bounds
(``TestExhaustiveProtocolChecks`` — proof-up-to-bound, with seeded-bug
mutation tests showing the harness actually catches dropped decrefs).
The big randomized sweeps that used to carry this weight remain as the
SLOW-lane belt-and-braces (larger pools, longer traces than the
explorer can enumerate). Plain ``random`` with fixed seeds —
deterministic, no external property-testing dependency."""
import random

import pytest

from paddle_tpu.analysis import protomodel

from paddle_tpu.models.decode_engine import (BlockLifetimeError,
                                             HostBlockPool,
                                             PromptPrefixCache,
                                             RadixBlockTree)


class TestExhaustiveProtocolChecks:
    """Every reachable interleaving at small bounds (the protomodel
    explorer) — the fast-lane replacement for sampling: refcount
    conservation in every state, drain-to-free from every state, no
    deadlock, no lifetime raise. The mutation tests seed a real bug
    class into one action and assert the harness CATCHES it with a
    minimal trace — a green exhaustive run means something only if a
    red one is demonstrably reachable."""

    def test_block_pool_every_interleaving_conserves_refcounts(self):
        r = protomodel.explore(protomodel.block_pool_protocol(
            n_blocks=2, n_lanes=2, pages=1))
        assert r.ok and not r.truncated, (
            r.counterexample and r.counterexample.format())

    def test_prefix_cache_every_interleaving_conserves_entries(self):
        r = protomodel.explore(protomodel.prefix_cache_protocol(
            n_entries=2, n_prompts=2, n_clients=2))
        assert r.ok and not r.truncated, (
            r.counterexample and r.counterexample.format())

    def test_radix_every_interleaving_conserves_holds(self):
        r = protomodel.explore(protomodel.radix_protocol(
            n_blocks=3, n_lanes=2))
        assert r.ok and not r.truncated, (
            r.counterexample and r.counterexample.format())

    def test_mutation_dropped_decref_is_caught(self):
        # seed the leak class PTA201 exists for: a retire path that
        # forgets to decref the lane's blocks. The explorer must
        # refute it with a minimal trace, via the refcount invariant
        # (the state lies about holds) and/or the drain leak check.
        proto = protomodel.block_pool_protocol(
            n_blocks=2, n_lanes=2, pages=1)

        def leaky_retire(s, li=0):
            # drops the hold WITHOUT releasing the refcount
            s["lanes"][li].update(blocks=[], shared=[])

        proto.actions = [
            a if not a.name.startswith("retire[0")
            else protomodel.Action(a.name, a.guard, leaky_retire)
            for a in proto.actions]
        r = protomodel.explore(proto)
        assert not r.ok and r.counterexample is not None
        assert r.counterexample.kind in ("invariant", "leak")
        assert "refcount" in r.counterexample.detail
        # minimal: alloc then the leaky retire, nothing longer
        assert len(r.counterexample.trace) == 2

    def test_mutation_double_release_is_caught_by_typestate(self):
        # the opposite bug: a release path that decrefs twice. The
        # REAL allocator's typestate machine must raise the named
        # BlockLifetimeError, surfacing as a `lifetime` violation.
        proto = protomodel.block_pool_protocol(
            n_blocks=2, n_lanes=1, pages=1)

        def double_retire(s):
            lane = s["lanes"][0]
            for b in lane["blocks"]:
                s["pool"].decref(b)
                s["pool"].decref(b)
            lane.update(blocks=[], shared=[])

        proto.actions = [
            a if not a.name.startswith("retire[0")
            else protomodel.Action(a.name, a.guard, double_retire)
            for a in proto.actions]
        r = protomodel.explore(proto)
        assert not r.ok and r.counterexample.kind == "lifetime"


class TestHostBlockPoolModel:
    @pytest.mark.slow
    def test_random_traces_keep_live_blocks_disjoint(self):
        for seed in range(8):
            rng = random.Random(1000 + seed)
            pool = HostBlockPool(rng.randint(1, 24))
            owned = {}          # lane -> set of blocks
            for _ in range(400):
                lane = rng.randrange(6)
                mine = owned.setdefault(lane, set())
                if rng.random() < 0.55:
                    b = pool.alloc()
                    if b is None:
                        assert pool.free_count == 0
                        continue
                    # alloc-disjoint: the block is live for NOBODY
                    for other, blocks in owned.items():
                        assert b not in blocks, (seed, lane, other)
                    mine.add(b)
                elif mine:
                    take = rng.sample(sorted(mine),
                                      rng.randint(1, len(mine)))
                    pool.free(take)
                    mine.difference_update(take)
                # global invariants after every step
                live = set().union(*owned.values()) if owned else set()
                assert pool.live_blocks() == live
                assert pool.in_use == len(live)
                assert pool.free_count + pool.in_use == pool.n_blocks

    def test_double_free_raises_named_error(self):
        pool = HostBlockPool(4)
        b = pool.alloc()
        pool.free([b])
        with pytest.raises(BlockLifetimeError, match="typestate"):
            pool.free([b])

    def test_free_of_unallocated_raises_named_error(self):
        # the satellite regression: this used to corrupt the free
        # list (the next alloc would hand one block to two lanes)
        pool = HostBlockPool(4)
        with pytest.raises(BlockLifetimeError):
            pool.free([2])
        with pytest.raises(BlockLifetimeError, match="outside"):
            pool.free([99])
        # a refused free leaves the pool consistent
        assert pool.free_count == 4 and pool.in_use == 0

    def test_failed_free_is_atomic(self):
        pool = HostBlockPool(4)
        a, b = pool.alloc(), pool.alloc()
        with pytest.raises(BlockLifetimeError):
            pool.free([a, a])   # second entry is a double free
        # NOTHING was freed: validation precedes mutation
        assert pool.typestate(a) == "exclusive"
        assert pool.typestate(b) == "exclusive"
        assert pool.free_count == 2
        pool.free([a, b])
        assert pool.free_count == 4

    def test_typestate_surface(self):
        pool = HostBlockPool(2)
        b = pool.alloc()
        assert pool.typestate(b) == "exclusive"
        pool.free([b])
        assert pool.typestate(b) == "free"


class TestPromptPrefixCacheModel:
    def _prompt(self, rng):
        return tuple(rng.randrange(50) for _ in range(4))

    @pytest.mark.slow
    def test_random_traces_keep_refcounts_and_eviction_legal(self):
        for seed in range(8):
            rng = random.Random(2000 + seed)
            pc = PromptPrefixCache(rng.randint(1, 6), chunk_tokens=2)
            refs = {}           # entry -> model refcount
            prompts = [self._prompt(rng) for _ in range(8)]
            for _ in range(300):
                p = rng.choice(prompts)
                r = rng.random()
                tier, entry = pc.lookup(p)
                if r < 0.5:
                    if tier == "hit":
                        e = pc.acquire_hit(p)
                        refs[e] = refs.get(e, 0) + 1
                    else:
                        before = dict(refs)
                        e = pc.acquire_fresh(p, partial=(
                            tier == "partial"))
                        if e is None:
                            # every entry pinned: nothing evictable
                            assert all(v > 0 for v in before.values())
                            assert len(before) >= pc.n_entries
                            continue
                        # fresh-exclusive: the entry was NOT live
                        # (eviction only touches unpinned entries)
                        assert before.get(e, 0) == 0, (seed, e)
                        refs[e] = 1
                        assert pc.refcount(e) == 1
                        assert pc.writable(e)
                        assert pc.typestate(e) == "exclusive"
                else:
                    live = [e for e, v in refs.items() if v > 0]
                    if live:
                        e = rng.choice(live)
                        pc.release(e)
                        refs[e] -= 1
                # invariants after every step
                for e, v in refs.items():
                    assert pc.refcount(e) == v and v >= 0
                    assert pc.is_shared(e) == (v > 1)
                    assert pc.writable(e) == (v <= 1)
                assert pc.in_use == sum(1 for v in refs.values()
                                        if v > 0)
                assert pc.in_use <= pc.n_entries

    def test_release_below_zero_raises_named_error(self):
        pc = PromptPrefixCache(2, chunk_tokens=2)
        e = pc.acquire_fresh((1, 2, 3))
        pc.release(e)
        with pytest.raises(BlockLifetimeError, match="refcount"):
            pc.release(e)

    def test_shared_entry_is_not_writable(self):
        # the host half of PTA192's read-only-while-shared: two lanes
        # share one prompt entry -> refcount 2 -> not writable; after
        # one release it returns to the exclusive (COW-legal) state
        pc = PromptPrefixCache(2, chunk_tokens=2)
        p = (5, 5, 5)
        e = pc.acquire_fresh(p)
        assert pc.typestate(e) == "exclusive" and pc.writable(e)
        assert pc.acquire_hit(p) == e
        assert pc.typestate(e) == "shared"
        assert pc.is_shared(e) and not pc.writable(e)
        pc.release(e)
        assert pc.typestate(e) == "exclusive" and pc.writable(e)

    def test_eviction_only_touches_unpinned(self):
        pc = PromptPrefixCache(2, chunk_tokens=2)
        p1, p2, p3 = (1, 1), (2, 2), (3, 3)
        e1 = pc.acquire_fresh(p1)
        e2 = pc.acquire_fresh(p2)
        # both pinned: a miss has nothing to evict
        assert pc.acquire_fresh(p3) is None
        pc.release(e1)
        # p1 now unpinned: it is the only legal victim
        e3 = pc.acquire_fresh(p3)
        assert e3 == e1 and pc.evictions == 1
        assert pc.lookup(p1) == ("miss", None)
        assert pc.lookup(p2)[0] == "hit"
        assert pc.refcount(e2) == 1


class TestRadixBlockTreeModel:
    """Randomized trace testing of the refcounted radix tree over
    HostBlockPool (the ISSUE 16 protocol): lanes acquire shared
    chains read-only + alloc exclusive tails, finished chains are
    inserted (the tree adopts with its OWN ref; existing node wins),
    eviction unpins tree-only leaves. The model tracks every holder
    of every block and cross-checks the pool's refcounts/typestates
    after every operation."""

    BS = 2

    def _histories(self, rng):
        """Per-prompt deterministic decode streams that SHARE a
        prefix and then branch (greedy decode determinism is what
        makes radix chains shareable at all): prompt -> two variants
         'a'/'b' diverging after a random number of chunks."""
        out = {}
        for p in range(3):
            prompt = (100 + p, 200 + p)
            common = [rng.randrange(3, 50)
                      for _ in range(self.BS * rng.randint(1, 4))]
            out[prompt] = {
                v: common + [rng.randrange(3, 50) + 50 * i
                             for i in range(self.BS * 5)]
                for i, v in enumerate(("a", "b"))}
        return out

    def _check(self, pool, tree, lanes):
        """Global cross-check: pool refcounts == model holder counts,
        writability == single ownership, lane TAILS disjoint."""
        holders = {b: 1 for b in tree.tree_blocks()}
        tails = []
        for ln in lanes.values():
            for b in ln["shared"] + ln["tail"]:
                holders[b] = holders.get(b, 0) + 1
            tails.append(set(ln["tail"]))
        for b in range(pool.n_blocks):
            want = holders.get(b, 0)
            assert pool.refcount(b) == want, (b, want,
                                              pool.refcount(b))
            assert (pool.typestate(b) != "free") == (want > 0)
            if want > 0:
                # refcount 1 <=> writable <=> exactly one holder
                assert pool.writable(b) == (want == 1)
        # live blocks never overlap across chains in the WRITABLE
        # position: exclusive tails are pairwise disjoint
        for i in range(len(tails)):
            for j in range(i + 1, len(tails)):
                assert not (tails[i] & tails[j]), (tails[i],
                                                   tails[j])
        assert pool.free_count + pool.in_use == pool.n_blocks

    @pytest.mark.slow
    def test_random_traces_hold_radix_invariants(self):
        for seed in range(6):
            rng = random.Random(3000 + seed)
            pool = HostBlockPool(rng.randint(10, 28))
            tree = RadixBlockTree(pool, self.BS)
            hist = self._histories(rng)
            lanes, next_lane = {}, 0
            for _ in range(250):
                r = rng.random()
                if r < 0.45:  # admit: acquire shared + alloc tail
                    prompt = rng.choice(list(hist))
                    var = rng.choice(("a", "b"))
                    n = rng.randrange(0, 10)
                    toks = hist[prompt][var][:n]
                    shared = tree.acquire(prompt, toks)
                    want_tail = rng.randint(1, 2)
                    tail = []
                    while len(tail) < want_tail:
                        b = pool.alloc()
                        if b is None:
                            break
                        tail.append(b)
                    if len(tail) < want_tail:
                        # exhausted: back out ATOMICALLY (the
                        # server's blocked-admission path)
                        for b in reversed(tail):
                            pool.decref(b)
                        tree.release(shared)
                    else:
                        lanes[next_lane] = {
                            "prompt": prompt, "var": var,
                            "shared": shared, "tail": tail}
                        next_lane += 1
                elif r < 0.75 and lanes:  # finish: insert + free
                    lid = rng.choice(list(lanes))
                    ln = lanes.pop(lid)
                    chain = ln["shared"] + ln["tail"]
                    # the lane decoded along its deterministic
                    # stream: every block in the chain is FULL
                    toks = hist[ln["prompt"]][ln["var"]][
                        :len(chain) * self.BS]
                    before_tree = tree.tree_blocks()
                    adopted = tree.insert(ln["prompt"], toks, chain)
                    # existing node wins: newly adopted blocks are
                    # exactly the chain blocks not already in a node
                    gained = tree.tree_blocks() - before_tree
                    assert len(gained) == adopted
                    assert gained <= set(chain)
                    tree.release(ln["shared"])
                    for b in reversed(ln["tail"]):
                        pool.decref(b)
                elif r < 0.9:  # evict
                    lane_held = {b for ln in lanes.values()
                                 for b in ln["shared"] + ln["tail"]}
                    before = pool.free_count
                    freed = tree.evict(rng.randint(1, 3))
                    assert pool.free_count == before + freed
                    # eviction never touches a pinned block
                    for b in lane_held:
                        assert pool.typestate(b) != "free", b
                else:  # release a lane WITHOUT inserting (failure/
                    # preemption path: nothing joins the tree)
                    if lanes:
                        lid = rng.choice(list(lanes))
                        ln = lanes.pop(lid)
                        tree.release(ln["shared"])
                        for b in reversed(ln["tail"]):
                            pool.decref(b)
                self._check(pool, tree, lanes)
            # drain: release every lane, then evict the whole tree —
            # the pool must come back to fully free (no leaks)
            for ln in lanes.values():
                tree.release(ln["shared"])
                for b in reversed(ln["tail"]):
                    pool.decref(b)
            tree.evict(pool.n_blocks)
            assert pool.free_count == pool.n_blocks
            assert tree.tree_blocks() == set()

    def test_refcounts_never_negative(self):
        pool = HostBlockPool(2)
        b = pool.alloc()
        pool.decref(b)
        with pytest.raises(BlockLifetimeError, match="negative"):
            pool.decref(b)
        with pytest.raises(BlockLifetimeError, match="refcount 0"):
            pool.incref(b)

    def test_shared_block_is_not_writable_cow_restores(self):
        # host half of PTA192: a first write into a shared block must
        # COW — the shared source is never writable; the fresh copy
        # is; decref'ing the source back to one owner restores its
        # writability
        pool = HostBlockPool(4)
        src = pool.alloc()
        pool.incref(src)                 # tree/another lane adopts
        assert not pool.writable(src)
        dst = pool.alloc()               # the COW destination
        assert pool.writable(dst)
        pool.decref(src)                 # the writing lane lets go
        assert pool.writable(src)        # sole owner again

    def test_strict_free_rejects_shared_blocks(self):
        # the legacy lane-release path must NOT yank a radix-adopted
        # block: free() is exclusive-only, decref is the radix-aware
        # release
        pool = HostBlockPool(2)
        b = pool.alloc()
        pool.incref(b)
        with pytest.raises(BlockLifetimeError, match="shared"):
            pool.free([b])
        assert pool.refcount(b) == 2     # the refused free mutated
        pool.decref(b)                   # nothing
        pool.free([b])

    def test_insert_underflow_is_atomic(self):
        pool = HostBlockPool(4)
        tree = RadixBlockTree(pool, 2)
        blocks = [pool.alloc(), pool.alloc()]
        with pytest.raises(BlockLifetimeError, match="radix insert"):
            tree.insert((1, 2), [5, 6, 7, 8, 9, 10], blocks)
        # NOTHING was adopted: validation precedes mutation
        assert tree.tree_blocks() == set()
        assert all(pool.refcount(b) == 1 for b in blocks)

    def test_existing_node_wins_duplicate_stays_lane_owned(self):
        # two lanes decode the SAME continuation (greedy twins): the
        # first insert adopts, the second adopts nothing and the
        # duplicate blocks remain the lane's to free normally
        pool = HostBlockPool(8)
        tree = RadixBlockTree(pool, 2)
        toks = [7, 8, 9, 10]
        a = [pool.alloc(), pool.alloc()]
        assert tree.insert((1,), toks, a) == 2
        b = [pool.alloc(), pool.alloc()]
        assert tree.insert((1,), toks, b) == 0
        assert tree.tree_blocks() == set(a)
        for blk in reversed(b):
            pool.decref(blk)             # duplicates: plain free
        for blk in reversed(a):
            pool.decref(blk)             # lane refs; tree's survive
        assert pool.in_use == 2          # the adopted chain lives on
        # a later acquire maps the surviving chain
        got = tree.acquire((1,), toks)
        assert got == a
        tree.release(got)

    def test_evict_deepest_leaf_first_never_interior(self):
        pool = HostBlockPool(8)
        tree = RadixBlockTree(pool, 2)
        toks = [1, 2, 3, 4, 5, 6]
        chain = [pool.alloc() for _ in range(3)]
        tree.insert((9,), toks, chain)
        for b in reversed(chain):
            pool.decref(b)               # lane gone; tree-only now
        # a lane pins the 2-block prefix: only the depth-3 leaf is
        # evictable, interior nodes under the pin never are
        held = tree.acquire((9,), toks[:4])
        assert held == chain[:2]
        assert tree.evict(99) == 1
        assert pool.typestate(chain[2]) == "free"
        assert tree.tree_blocks() == set(chain[:2])
        tree.release(held)
        assert tree.evict(99) == 2       # unpinned: deepest first
        assert pool.free_count == pool.n_blocks


def _evict_by_walk(tree, need):
    """The eviction ``RadixBlockTree`` had before it kept an index of
    its leaves, kept here as the ORACLE: one pass over every node of
    every root for each block freed, the deepest tree-only leaf first
    (``_roots``' insertion order between roots; inside a root the
    first leaf of that depth a descent through each node's newest
    child meets). Reads and edits the node structure only — none of
    the index's fields. Returns the freed blocks in order."""
    freed = []
    while len(freed) < need:
        victim = None
        for root in tree._roots.values():
            stack = [(c, 1) for c in root.children.values()]
            best = None
            while stack:
                n, d = stack.pop()
                if n.children:
                    stack.extend((c, d + 1)
                                 for c in n.children.values())
                elif tree.pool.refcount(n.block) == 1:
                    if best is None or d > best[1]:
                        best = (n, d)
            if best is not None and (
                    victim is None or best[1] > victim[1]):
                victim = best
        if victim is None:
            break
        node = victim[0]
        del node.parent.children[node.chunk]
        tree.pool.decref(node.block)
        freed.append(node.block)
    for key in [k for k, r in tree._roots.items() if not r.children]:
        del tree._roots[key]
    return freed


class _RadixSystem:
    """One pool, one tree and the lanes over them, driven by a history
    of operations that names lanes and token streams only — so two
    systems fed one history stay comparable block for block."""

    def __init__(self, n_blocks, block_size, evict):
        self.pool = HostBlockPool(n_blocks)
        self.tree = RadixBlockTree(self.pool, block_size)
        self.lanes = {}
        self._evict = evict
        self.freed = []           # every evicted block, in order

    def evict(self, need):
        before = len(self.pool._free)
        got = self._evict(self.tree, need)
        self.freed += self.pool._free[before:]
        return got

    def admit(self, lid, prompt, toks, want_tail):
        shared = self.tree.acquire(prompt, toks)
        tail = []
        while len(tail) < want_tail:
            b = self.pool.alloc()
            if b is None and self.evict(1):
                # the server's _alloc_block_locked: cache before work
                b = self.pool.alloc()
            if b is None:
                break
            tail.append(b)
        if len(tail) < want_tail:
            for b in reversed(tail):
                self.pool.decref(b)
            self.tree.release(shared)
            return
        self.lanes[lid] = {"prompt": prompt, "toks": toks,
                           "shared": shared, "tail": tail}

    def insert(self, lid):
        ln = self.lanes[lid]
        chain = ln["shared"] + ln["tail"]
        bs = self.tree.block_size
        return self.tree.insert(ln["prompt"],
                                ln["toks"][:len(chain) * bs], chain)

    def retire(self, lid):
        ln = self.lanes.pop(lid)
        self.tree.release(ln["shared"])
        for b in reversed(ln["tail"]):
            self.pool.decref(b)

    def snapshot(self):
        return (list(self.pool._free), list(self.pool._refs),
                list(self.pool._state), self.tree.tree_blocks(),
                protomodel.tree_fingerprint(self.tree),
                {k: (v["shared"], v["tail"])
                 for k, v in self.lanes.items()}, self.freed)


class TestRadixLeafIndex:
    """``RadixBlockTree.evict`` takes its victims from an index of the
    leaves and walks nothing. Held to the walk it replaced: the same
    blocks freed in the same order under one random history, and a
    bounded number of leaves examined for each block freed."""

    N_PROMPTS = 4

    def _streams(self, rng, bs):
        """prompt -> token streams that share a trunk and fork at
        different depths (divergent children under one node, at the
        root's first chunk and deeper)."""
        out = {}
        for p in range(self.N_PROMPTS):
            trunk = [rng.randrange(3, 90)
                     for _ in range(bs * rng.randint(0, 3))]
            fork = trunk + [rng.randrange(100, 190)
                            for _ in range(bs * rng.randint(1, 2))]
            out[(100 + p, 200 + p)] = [
                base + [rng.randrange(3, 90) + 200 * i
                        for _ in range(bs * 6)]
                for i, base in enumerate((trunk, trunk, fork, fork))]
        return out

    @pytest.mark.parametrize("block_size", [1, 16])
    @pytest.mark.parametrize("seed", range(8))
    def test_index_frees_what_the_walk_frees_in_its_order(
            self, seed, block_size):
        rng = random.Random(7000 + 31 * seed + block_size)
        n_blocks = rng.randint(14, 40)
        idx = _RadixSystem(n_blocks, block_size,
                           lambda tree, need: tree.evict(need))
        ref = _RadixSystem(n_blocks, block_size,
                           lambda tree, need: len(
                               _evict_by_walk(tree, need)))
        streams = self._streams(rng, block_size)
        next_lane = 0
        seen = {"pinned_leaf": 0, "pinned_interior": 0, "ties": 0,
                "roots": 0, "evicted": 0}
        for step in range(400):
            r = rng.random()
            live = sorted(idx.lanes)
            if r < 0.3 and len(live) < 5:   # admit: map a shared
                prompt = rng.choice(sorted(streams))  # prefix, own
                toks = rng.choice(streams[prompt])    # a tail
                toks = toks[:block_size * rng.choice(
                    (0, 2, 3, 3, 4, 4, 9))]
                tail = rng.randint(1, 3)
                for s in (idx, ref):
                    s.admit(next_lane, prompt, toks, tail)
                next_lane += 1
            elif r < 0.4 and live:    # harvest; the lane lives on,
                lid = rng.choice(live)  # so the new tip is PINNED
                assert idx.insert(lid) == ref.insert(lid)
            elif r < 0.7 and live:    # the lane's decrefs, which the
                lid = rng.choice(live)  # tree is never told about
                if rng.random() < 0.6:  # (the server harvests first)
                    assert idx.insert(lid) == ref.insert(lid)
                for s in (idx, ref):
                    s.retire(lid)
            else:
                need = n_blocks if r > 0.98 else rng.randint(1, 3)
                self._census(idx, seen)
                assert idx.evict(need) == ref.evict(need), step
            a, b = idx.snapshot(), ref.snapshot()
            assert a == b, (step, a, b)
            assert idx.tree.n_nodes == len(idx.tree.tree_blocks())
        for s in (idx, ref):
            for lid in sorted(s.lanes):
                s.retire(lid)
            s.evict(n_blocks)
            assert s.pool.free_count == n_blocks
            assert s.tree.tree_blocks() == set() and not s.tree._roots
        assert idx.freed == ref.freed and idx.tree.n_nodes == 0
        assert idx.tree.evicted_blocks == len(idx.freed)
        # the history really held what the index must get right
        seen["evicted"] = len(idx.freed)
        assert all(seen.values()), seen

    @staticmethod
    def _census(s, seen):
        """Count, before an eviction, the shapes the history is meant
        to contain (so a generator that stops making them fails)."""
        leaves, pinned_interior = [], 0
        seen["roots"] += len(s.tree._roots) > 1
        for root in s.tree._roots.values():
            stack = [(c, 1) for c in root.children.values()]
            while stack:
                n, d = stack.pop()
                stack.extend((c, d + 1) for c in n.children.values())
                pinned = s.pool.refcount(n.block) > 1
                if not n.children:
                    leaves.append((d, pinned))
                elif pinned:
                    pinned_interior += 1
        seen["pinned_interior"] += pinned_interior
        seen["pinned_leaf"] += sum(p for _, p in leaves)
        free = [d for d, p in leaves if not p]
        seen["ties"] += bool(free) and free.count(max(free)) > 1

    def test_regrown_old_root_goes_before_a_younger_roots_tip(self):
        # the tie the order key exists for: "the order in which nodes
        # became leaves" would free the younger root's tip first
        idx = _RadixSystem(12, 1, lambda t, n: t.evict(n))
        ref = _RadixSystem(12, 1,
                           lambda t, n: len(_evict_by_walk(t, n)))
        for s in (idx, ref):
            s.admit(0, (1,), [5, 6, 7], 3)
            s.insert(0)
            s.retire(0)
            assert s.evict(1) == 1        # the old root's chain trimmed
            s.admit(1, (2,), [8, 9, 10], 3)
            s.insert(1)                   # a younger root, depth 3
            s.retire(1)
            s.admit(2, (1,), [5, 6, 7], 1)
            s.insert(2)                   # the old root grows again
            s.retire(2)
            s.evict(2)
        assert idx.freed == ref.freed
        assert idx.snapshot() == ref.snapshot()

    def test_insert_that_raises_midway_leaves_its_nodes_evictable(
            self):
        # a chain whose third block was already freed (a caller's
        # fault): incref raises after two adoptions, and those two
        # must still be reclaimable — the second is a leaf the index
        # has to know, or its block is lost to the pool for good
        pool = HostBlockPool(6)
        tree = RadixBlockTree(pool, 1)
        chain = [pool.alloc() for _ in range(3)]
        pool.decref(chain[2])
        with pytest.raises(BlockLifetimeError, match="refcount 0"):
            tree.insert((1,), [5, 6, 7], chain)
        assert tree.tree_blocks() == set(chain[:2])
        assert tree.n_nodes == 2 and tree.adoptions == 2
        with pytest.raises(BlockLifetimeError, match="refcount 0"):
            tree.insert((2,), [5], [chain[2]])
        assert (2,) not in tree._roots   # no empty root left behind
        for b in chain[:2]:
            pool.decref(b)
        assert tree.evict(6) == 2
        assert pool.free_count == 6 and not tree._roots

    @staticmethod
    def _steady_state(n_chains=51, depth=15, bs=16, n_blocks=1280):
        """The serve cells' tree once the pool is full: 51 retired
        generations of 15 full blocks each, 765 nodes in 1,280."""
        pool = HostBlockPool(n_blocks)
        tree = RadixBlockTree(pool, bs)

        def adopt(p):
            chain = []
            for _ in range(depth):
                b = pool.alloc()
                if b is None:
                    assert tree.evict(1) == 1
                    b = pool.alloc()
                chain.append(b)
            toks = [(p * 7 + i) % 251 for i in range(depth * bs)]
            assert tree.insert((p,), toks, chain) == depth
            for b in reversed(chain):
                pool.decref(b)
            return toks

        toks = [adopt(p) for p in range(n_chains)]
        assert tree.n_nodes == n_chains * depth == \
            len(tree.tree_blocks())
        return pool, tree, adopt, toks

    def test_evict_32_examines_32_leaves_and_the_pinned(self):
        pool, tree, _, toks = self._steady_state()
        held = [tree.acquire((p,), toks[p]) for p in range(5)]
        assert all(len(h) == 15 for h in held)   # five pinned tips
        c0 = tree.evict_candidates
        assert tree.evict(32) == 32
        assert tree.evict_candidates - c0 <= 32 + len(held)
        assert tree.evict_calls == 1
        assert tree.n_nodes == 765 - 32 == len(tree.tree_blocks())
        for h in held:
            tree.release(h)

    def test_steady_eviction_examines_under_two_leaves_a_block(self):
        pool, tree, adopt, _ = self._steady_state()
        c0, e0 = tree.evict_candidates, tree.evicted_blocks
        for i in range(600):
            assert tree.evict(1) == 1
            if i % 15 == 14:
                adopt(1000 + i)    # a retirement: 15 blocks, of which
                #                    the pool's free list gives some
        freed = tree.evicted_blocks - e0
        assert freed >= 600
        assert (tree.evict_candidates - c0) / freed < 2
        # the index holds no more than a node an entry
        assert len(tree._leaves) <= tree.n_nodes
