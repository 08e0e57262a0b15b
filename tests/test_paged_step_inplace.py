"""The decode step updates the KV pool in place: compiled ahead of time
for a described v5e it holds no copy of the pool, whether it attends
through the block table or over a gathered span, and the one-row
write that makes that possible puts the same values in the same places
as the form it replaced."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from defer_tpu.models import gpt
from defer_tpu.models.llama import mistral_config
from defer_tpu.runtime.paged import PagedDecodeServer, _pool_write_rows
from tests.test_cohere2_moe import TOY, family

# The toy of window and full layers over experts at the head size the
# flash-decode kernel is built for, its table long enough for rungs
# the kernel takes; two periods, so that the layer loop is a loop.
KINDS = {
    **TOY, "head_dim": 128, "hidden_size": 256, "intermediate_size": 128,
    "num_attention_heads": 4, "max_position_embeddings": 1024,
    "sliding_window": 256, "vocab_size": 512,
}


def dense_gqa():
    return gpt.GptDecoder(
        mistral_config(
            num_layers=4, dim=512, num_heads=4, num_kv_heads=2, ffn_dim=1024,
            vocab_size=512, max_len=1024, window=1024,
        ),
        compute_dtype=jnp.bfloat16,
    )


def kinds_and_experts():
    return family.build_decoder(KINDS)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_the_chip(monkeypatch):
    """What is compiled for a described chip goes to the persistent
    cache and cannot be read back without one: off for the test. The
    decode kernel's switch is read at trace time and answers for this
    host: the test answers for the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(gpt, "_flash_decode_mode", lambda: "tpu")
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


POOL_SIZED = ("copy", "dynamic-update-slice", "dynamic-slice", "custom-call")


def step_for_the_chip(make, one_chip):
    """A server over `make()`'s stack with a bf16 pool of 512 blocks,
    and `compiled(cols)`: its decode step compiled for a described v5e
    on a table of `cols` columns."""
    dec = make()
    nb, bs, b = 512, 16, 8
    shapes = jax.eval_shape(dec.init, jax.random.key(0))
    srv = PagedDecodeServer(
        dec,
        jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.bfloat16), shapes),
        num_blocks=nb, block_size=bs, max_batch=b,
    )
    assert srv.pool_k.dtype == jnp.bfloat16

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def compiled(cols):
        return srv._build_step().lower(
            jax.tree.map(on_chip, srv.params), on_chip(srv.pool_k),
            on_chip(srv.pool_v), i32(b, cols), i32(b), i32(b, 1), i32(b),
        ).compile()

    return srv, compiled


def assert_holds_no_copy_of_the_pool(srv, program):
    """No instruction of the optimised program makes an array of the
    pool's shape or of one layer's slice of it but the scatters that
    write the new rows (a `custom-call` of that shape is XLA's
    `AllocateBuffer`: a second pool), the pool keeps the layout it is
    stored in, and the program's temporaries are under one pool's
    bytes."""
    text = program.as_text()
    assert "tpu_custom_call" in text  # the kernel's step, as on the chip
    layers, nb, hkv, bs, dh = srv.pool_k.shape
    shaped = re.compile(
        rf"= bf16\[(?:{layers}|1),{nb},{hkv},{bs},{dh}\]\{{([\d,]+)[^}}]*\}} "
        r"([\w\-]+)\("
    )
    found = shaped.findall(text)
    assert {op for _, op in found} >= {"scatter"}, found
    assert not [f for f in found if f[1] in POOL_SIZED], found
    assert {layout for layout, _ in found} == {"4,3,2,1,0"}, found
    one_pool = srv.pool_k.size * srv.pool_k.dtype.itemsize
    assert program.memory_analysis().temp_size_in_bytes < one_pool


def gathered(self, cols):
    """`PagedDecodeServer._attend` pinned to the gathered span."""
    return "gathered"


@pytest.mark.parametrize("make", [dense_gqa, kinds_and_experts])
def test_the_step_compiled_for_a_v5e_holds_no_copy_of_the_pool(
    make, one_chip, compiled_for_the_chip, monkeypatch
):
    """The step over a gathered span (`flash_decode` over the table's
    pages made contiguous: the int8 pool, a rung of no whole 256-row
    chunks) holds no copy of the pool (`assert_holds_no_copy_of_the_pool`)."""
    monkeypatch.setattr(PagedDecodeServer, "_attend", gathered)
    srv, compiled = step_for_the_chip(make, one_chip)
    program = compiled(srv._rungs[0])
    text = program.as_text()
    assert "flash_decode" in text and "table_flash_decode" not in text
    assert_holds_no_copy_of_the_pool(srv, program)


@pytest.mark.parametrize("make", [dense_gqa, kinds_and_experts])
def test_the_step_through_the_table_holds_no_copy_of_the_pool(
    make, one_chip, compiled_for_the_chip, monkeypatch
):
    """On a table of whole 256-row chunks the step attends through the
    table (`table_flash_decode` reads the carried pool) and holds no
    copy of the pool either; on the whole table its temporaries are
    under those of the same step over a gathered span."""
    srv, compiled = step_for_the_chip(make, one_chip)
    assert srv._attend(srv._rungs[0]) == "table"
    program = compiled(srv._rungs[0])
    assert "table_flash_decode" in program.as_text()
    assert_holds_no_copy_of_the_pool(srv, program)

    def temp(program):
        return program.memory_analysis().temp_size_in_bytes

    whole = srv._rungs[-1]
    through = temp(compiled(whole))
    with monkeypatch.context() as m:
        m.setattr(PagedDecodeServer, "_attend", gathered)
        assert temp(compiled(whole)) > through


def recurrent_and_attention():
    """Family `qwen3_next` at the sizes its two kernels are built for
    (head size 256 in `flash_decode`, 128 x 128 states in `gdn_step`),
    two periods of [linear, linear, linear, full]."""
    from perfbench import harness
    from tests.test_qwen3_next import REPO, TOY as HYBRID

    qwen3_next = harness.load_module(
        f"{REPO}/perfbench/families/qwen3_next.py"
    )
    return qwen3_next.build_decoder({
        **HYBRID, "hidden_size": 256, "head_dim": 256,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_num_key_heads": 4, "linear_num_value_heads": 8,
        "max_position_embeddings": 1024, "vocab_size": 512,
    })


def test_the_step_compiled_for_a_v5e_holds_no_copy_of_the_state_pool(
    one_chip, compiled_for_the_chip
):
    """The recurrent layers' state pool rides in the layer loop's carry
    beside the K/V pool and is donated: the `gdn_step` kernel rewrites
    one layer's cells where they lie (its output aliases its operand),
    no instruction makes a second array of the pool's shape or of a
    layer's slice of it, and the program's temporaries stay under the
    state pool's bytes and under one K/V pool's."""
    dec = recurrent_and_attention()
    # 32 slots: a state pool of 100 MB, which the compiler cannot keep
    # in its on-chip memory (a pool of 8 slots it moves there and back).
    nb, bs, b = 2048, 16, 32
    shapes = jax.eval_shape(dec.init, jax.random.key(0))
    srv = PagedDecodeServer(
        dec,
        jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.bfloat16), shapes),
        num_blocks=nb, block_size=bs, max_batch=b,
    )
    assert srv.pool_k.shape == (2, nb, 2, bs, 256)
    assert srv.pool_k.dtype == jnp.bfloat16
    state, rows = srv.pool_state
    assert state.shape == (6, b, 8, 128, 128) and state.dtype == jnp.float32

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    program = srv._build_step().lower(
        jax.tree.map(on_chip, srv.params), on_chip(srv.pool_k),
        on_chip(srv.pool_v), i32(b, srv._rungs[0]), i32(b), i32(b, 1), i32(b),
        jax.tree.map(on_chip, srv.pool_state),
    ).compile()
    text = program.as_text()
    made = set(re.findall(
        rf"= f32\[(?:6|1),{b},8,128,128\]\{{[^}}]*\}} ([\w\-]+)\(", text
    ))
    # The kernel's aliased output is read out of its tuple; nothing
    # else makes an array of the pool's shape or of a layer's slice.
    assert made <= {"get-tuple-element", "parameter"}, made
    assert len(re.findall(r" custom-call\([^\n]*gdn_step", text)) >= 3
    assert "flash_decode" in text
    temp = program.memory_analysis().temp_size_in_bytes
    assert temp < state.size * 4
    assert temp < srv.pool_k.size * 2
    # Every pool is handed back in the buffer it came in.
    assert program.memory_analysis().alias_size_in_bytes >= (
        2 * srv.pool_k.size * 2 + state.size * 4 + rows.size * 4
    )


@pytest.mark.parametrize("layer", [None, 2])
def test_the_head_row_write_is_the_row_write(layer):
    """`_pool_write_rows` on an fp pool against the form it replaced,
    `.at[dest, :, rowi, :].set(val)`, bit for bit, on a layer's slice
    and on the whole pool at a layer; distinct destinations (live
    blocks have one owner; duplicates are the trash block's)."""
    rng = np.random.default_rng(3)
    nb, hkv, bs, dh, n = 12, 3, 4, 8, 5
    shape = (nb, hkv, bs, dh) if layer is None else (4, nb, hkv, bs, dh)
    pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    dest = jnp.asarray(rng.permutation(nb)[:n], jnp.int32)
    rowi = jnp.asarray(rng.integers(0, bs, n), jnp.int32)
    val = jnp.asarray(rng.standard_normal((n, hkv, dh)), jnp.bfloat16)
    got = jax.jit(_pool_write_rows)(pool, dest, rowi, val, layer)
    if layer is None:
        want = pool.at[dest, :, rowi, :].set(val)
    else:
        want = pool.at[layer, dest, :, rowi, :].set(val)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )
    assert not np.array_equal(
        np.asarray(got, np.float32), np.asarray(pool, np.float32)
    )
