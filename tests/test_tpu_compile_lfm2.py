"""The Pallas kernels of the LFM2-MoE step (and, since PR 38, the delta
rule and the wide paged reads of the Olmo-Hybrid serve programs)
compiled for a TPU v5e that
is described, not attached, at the widths the benchmark cell runs: what
the chip's compiler would refuse (a tile that does not fit its fast
memory, a misaligned slice) fails here at no chip time. Nothing runs,
so nothing here says anything about a result or a time. One file, and
the topology is described inside a fixture: only the worker that is
given this file loads the TPU's library."""
import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache and cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_names(compiled):
    import re

    return set(re.findall(r'%([A-Za-z_]+?)[._\d]* = [^\n]*'
                          r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


def test_flash_attention_8192_with_shared_key_value_heads(one_chip):
    from paddle_tpu.ops.pallas import attention

    def step(q, k, v):
        return jax.value_and_grad(lambda *a: attention.flash_attention(
            *a, 0.125, True).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
    compiled = jax.jit(step).lower(
        _spec(one_chip, (1, 32, 8192, 64)),
        _spec(one_chip, (1, 8, 8192, 64)),
        _spec(one_chip, (1, 8, 8192, 64))).compile()
    names = _kernel_names(compiled)
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        assert any(kernel in n for n in names), names


@pytest.mark.parametrize("rows", [8192, 32768])
def test_grouped_products_of_the_expert_layer(one_chip, rows,
                                              monkeypatch):
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(gm, "on_tpu", lambda: True)

    def step(xs, w13, w2, sizes):
        def loss(xs, w13, w2):
            h = gm.grouped_matmul(xs, w13, sizes)
            a = (jax.nn.silu(h[:, :1536].astype(jnp.float32))
                 * h[:, 1536:].astype(jnp.float32)).astype(xs.dtype)
            return gm.grouped_matmul(a, w2, sizes).astype(
                jnp.float32).sum()
        return jax.value_and_grad(loss, (0, 1, 2))(xs, w13, w2)
    compiled = jax.jit(step).lower(
        _spec(one_chip, (rows, 2048)), _spec(one_chip, (8, 2048, 3072)),
        _spec(one_chip, (8, 1536, 2048)),
        _spec(one_chip, (9,), jnp.int32)).compile()
    names = _kernel_names(compiled)
    assert any("tgmm" in n for n in names), names
    assert any("gmm" in n and "tgmm" not in n for n in names), names


def test_paged_decode_attention_at_the_serve_cells_size(one_chip):
    """transformer-big-serve: 33 lanes, 16 heads of 64, 1,280 blocks
    of 16 cells, 6 layers. The pools go in as stored and the layers
    share one lowering of the kernel (a cell binds up to 19 serve
    programs at set-up)."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    rows, heads, dim, bs, pages, blocks, layers = 33, 16, 64, 16, 16, \
        1280, 6

    def tick(q, pools, tab, pos):
        for li in range(layers):
            q = q + pa.paged_decode_attention(
                q, pools[2 * li], pools[2 * li + 1], tab, pos,
                block_size=bs, n_heads=heads, scale=dim ** -0.5)
        return q
    lowered = jax.jit(tick).lower(
        _spec(one_chip, (rows, 1, heads * dim), jnp.float32),
        [_spec(one_chip, (blocks * bs, heads * dim), jnp.float32)
         for _ in range(2 * layers)],
        _spec(one_chip, (rows, pages), jnp.int32),
        _spec(one_chip, (rows,), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert _kernel_names(compiled) == {"paged_decode_attention"}
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == layers
    # nothing of a dense view's size, and no pool staged or copied
    for shape in ("f32[33,16,256,64]", "f32[8448,1024]",
                  "f32[33,256,1024]"):
        assert shape not in text, shape
    assert "copy-start" not in text


# ---------------------------------------------------------------------
# the GLM-5.2 serve cell's paged routes (ops/paged_ops.py) at the
# cell's widths: 33 lanes, a table of 576 pages of 64 positions over
# 8,192 blocks, a latent row of 576 and an indexer key of 128 numbers,
# 2,048 selected of 36,864 positions
# ---------------------------------------------------------------------
GLM = dict(rows=33, pages=576, bs=64, blocks=8192, latent=576, rkv=512,
           heads=64, hi=32, di=128, topk=2048)


def _temp_gb(compiled):
    return compiled.memory_analysis().temp_size_in_bytes / 1e9


def test_glm_decode_tick_indexer_selection_and_sparse_attention(one_chip):
    from paddle_tpu.ops import paged_ops as P

    g = GLM
    cells = g["blocks"] * g["bs"]

    def tick(qi, w, ipool, q, pool, tab, pos):
        s = P.indexer_scores(qi, w, ipool, tab, pos, g["bs"])
        val, idx = jax.lax.top_k(s, g["topk"])
        sel = jnp.where(val > -jnp.inf, idx, -1).astype(jnp.int32)
        return P.sparse_latent_attention_reference(
            q, pool, tab, sel, g["bs"], g["rkv"], 0.0625)
    compiled = jax.jit(tick).lower(
        _spec(one_chip, (g["rows"], g["hi"], g["di"])),
        _spec(one_chip, (g["rows"], g["hi"]), jnp.float32),
        _spec(one_chip, (cells, g["di"])),
        _spec(one_chip, (g["rows"], g["heads"], g["latent"])),
        _spec(one_chip, (cells, g["latent"])),
        _spec(one_chip, (g["rows"], g["pages"]), jnp.int32),
        _spec(one_chip, (g["rows"],), jnp.int32)).compile()
    # a lane's keys and scores and its selected rows (and, compiled
    # for a chip that is not there, one relayout of the pool from the
    # layout the compiler would like its argument in)
    assert _temp_gb(compiled) < 1.5


@pytest.mark.parametrize("chunk", [64, 1024])
def test_glm_prefill_chunk_threshold_and_dense_attention(one_chip, chunk):
    from paddle_tpu.ops import paged_ops as P

    g = GLM
    cells = g["blocks"] * g["bs"]

    def chunk_fn(qi, w, ipool, q, pool, tab, pos):
        s = P.indexer_scores(qi, w, ipool, tab, pos, g["bs"])
        thr = P.kth_largest(s, g["topk"])
        return P.dense_masked_latent_attention(
            q, pool, tab, s, thr, g["topk"], g["bs"], g["rkv"], 0.0625)
    compiled = jax.jit(chunk_fn).lower(
        _spec(one_chip, (chunk, g["hi"], g["di"])),
        _spec(one_chip, (chunk, g["hi"]), jnp.float32),
        _spec(one_chip, (cells, g["di"])),
        _spec(one_chip, (chunk, g["heads"], g["latent"])),
        _spec(one_chip, (cells, g["latent"])),
        _spec(one_chip, (1, g["pages"]), jnp.int32),
        _spec(one_chip, (chunk,), jnp.int32)).compile()
    # a block of queries at a time: scores of 128 queries by 32 heads,
    # then of 16 queries by 64 heads, over 36,864 positions
    assert _temp_gb(compiled) < 2.0


def test_prompt_table_read_at_the_serve_cells_size(one_chip):
    """The cross-attention read of the same cell: 33 lanes each on one
    of 129 prompt entries of 256 rows, the tables in as stored
    (``[E+1, S, H*Dh]``) and merged into rows on the way to the
    kernel, one block of 256 rows a lane, every lane at position 255.
    The self and the cross read of a layer share the kernel's name
    and a program holds one lowering of each shape."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    rows, heads, dim, seq, entries, layers = 33, 16, 64, 256, 129, 6
    width = heads * dim

    def tick(q, tables, ref):
        last = jnp.full((rows,), seq - 1, jnp.int32)
        for li in range(layers):
            q = q + pa.paged_decode_attention(
                q, tables[2 * li].reshape(-1, width),
                tables[2 * li + 1].reshape(-1, width),
                ref.reshape(rows, 1), last, block_size=seq,
                n_heads=heads, scale=dim ** -0.5)
        return q
    lowered = jax.jit(tick).lower(
        _spec(one_chip, (rows, 1, width), jnp.float32),
        [_spec(one_chip, (entries, seq, width), jnp.float32)
         for _ in range(2 * layers)],
        _spec(one_chip, (rows,), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert _kernel_names(compiled) == {"paged_decode_attention"}
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == layers
    # no gathered copy of the lanes' entries, head-major or as rows,
    # and merging entries into rows moves nothing: no copy of a table
    for shape in ("f32[33,16,256,64]", "f32[33,256,1024]",
                  "f32[33,1,256,1024]"):
        assert shape not in text, shape
    assert "copy-start" not in text
    import re

    assert not re.search(r"= f32\[(129,256|33024),1024\]\S* copy\(",
                         text)


# ---------------------------------------------------------------------
# Olmo-Hybrid's serve programs (PR 38): the delta rule and the tick's
# full attention over bfloat16 pools of 3,840 numbers a position, at the
# widths of `serve_olmoh_sessions_32`; what each holds beside its
# arguments is bounded, which a gather of every lane's whole table was
# not (4.4 GB, refused on the chip for the memory)
# ---------------------------------------------------------------------
class _Ctx:
    def __init__(self, attrs, **inputs):
        self.inputs, self.attrs = inputs, attrs

    def input(self, slot):
        return self.inputs.get(slot)

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


_H, _DK, _DV, _ROWS = 30, 96, 192, 33


def _delta_args(one_chip, rows):
    return [_spec(one_chip, (rows, _H * _DK)),
            _spec(one_chip, (rows, _H * _DK)),
            _spec(one_chip, (rows, _H * _DV)),
            _spec(one_chip, (rows, _H)), _spec(one_chip, (rows, _H)),
            _spec(one_chip, (_H,), jnp.float32),
            _spec(one_chip, (_H,), jnp.float32),
            _spec(one_chip, (_ROWS, _H, _DK, _DV), jnp.float32)]


def _delta_ctx(args, attrs, **where):
    return _Ctx(attrs, **dict(zip(("Q", "K", "V", "Beta", "A", "DtBias",
                                   "ALog", "State"), args)), **where)


def test_the_chunked_delta_rule_of_a_chunk_of_2048(one_chip):
    from paddle_tpu.ops import delta_ops

    def chunk(*args):
        *args, lane, length, pos = args
        return delta_ops.gated_delta_chunk(_delta_ctx(
            args, {"block": 64, "allow_neg_eigval": True}, Lane=lane,
            Len=length, Pos=pos))
    one = _spec(one_chip, (1,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(7,)).lower(
        *_delta_args(one_chip, 2048), one, one, one).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_delta_rule_step_of_every_lane_in_place(one_chip):
    from paddle_tpu.ops import delta_ops

    def step(*args):
        *args, gate, pos = args
        return delta_ops.gated_delta_step(_delta_ctx(
            args, {"allow_neg_eigval": True}, Gate=gate, Pos=pos))
    compiled = jax.jit(step, donate_argnums=(7,)).lower(
        *_delta_args(one_chip, _ROWS),
        _spec(one_chip, (_ROWS,), jnp.float32),
        _spec(one_chip, (_ROWS,), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    # the state is written where it is stored: 73 MB aliased (97 MB as
    # the chip lays it out, rows of 192 on tiles of 128 lanes), no copy
    assert memory.alias_size_in_bytes >= _ROWS * _H * _DK * _DV * 4
    assert memory.temp_size_in_bytes < 0.1e9


def test_a_tick_reads_wide_pools_one_lane_at_a_time(one_chip):
    from paddle_tpu.ops import paged_ops

    blocks, size, pages, width = 3584, 64, 144, 3840

    def attend(q, pool_k, pool_v, tab, pos):
        return paged_ops.paged_attention_by_lane(
            q, pool_k, pool_v, tab, pos, block_size=size, n_heads=30,
            n_kv_heads=30, scale=128 ** -0.5)
    compiled = jax.jit(attend).lower(
        _spec(one_chip, (_ROWS, 1, width)),
        _spec(one_chip, (blocks * size, width)),
        _spec(one_chip, (blocks * size, width)),
        _spec(one_chip, (_ROWS, pages), jnp.int32),
        _spec(one_chip, (_ROWS,), jnp.int32)).compile()
    # one lane's whole table, keys and values: 2 x 70.8 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_a_tick_streams_wide_pools_through_the_kernel(one_chip,
                                                      monkeypatch):
    """The same tick's two full-attention layers through the op, routed
    as on a TPU (the described chip's process sees the CPU backend, so
    the test says it is on one): one Mosaic kernel a layer, streaming
    each lane's live blocks, no float32 ``[*, 30, 128]`` relayout of a
    lane's keys and values, and nothing of the by-lane route's size
    held beside the arguments."""
    import re

    from paddle_tpu.ops import paged_ops, pallas

    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    blocks, size, pages, width, layers = 3584, 64, 144, 3840, 2
    attrs = {"block_size": size, "n_heads": 30, "n_kv_heads": 30,
             "scale": 128 ** -0.5}

    def tick(q, pools, tab, pos):
        for li in range(layers):
            q = q + paged_ops.paged_decode_attention(_Ctx(
                attrs, Q=q, PoolK=pools[2 * li], PoolV=pools[2 * li + 1],
                Table=tab, Pos=pos))
        return q
    compiled = jax.jit(tick).lower(
        _spec(one_chip, (_ROWS, 1, width)),
        [_spec(one_chip, (blocks * size, width))
         for _ in range(2 * layers)],
        _spec(one_chip, (_ROWS, pages), jnp.int32),
        _spec(one_chip, (_ROWS,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_names(compiled) == {"paged_decode_attention_streamed"}
    assert text.count('custom_call_target="tpu_custom_call"') == layers
    assert not re.search(r"f32\[\d+,30,128\]", text)
    # the by-lane route holds a lane's whole table, keys and values:
    # up to 0.2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01e9
