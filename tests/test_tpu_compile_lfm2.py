"""The Pallas kernels of the LFM2-MoE step compiled for a TPU v5e that
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
