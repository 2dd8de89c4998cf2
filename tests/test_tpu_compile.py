"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at Yi-6B widths (32 q heads, 4 kv heads, head_dim 128,
d_model 4096, bf16), with the slot and page geometry of ``chip_smoke.py``.

No chip is needed: the TPU compiler is installed and compiles for a
topology it is told about. That catches what interpret mode on the CPU
cannot, such as a block shape the Mosaic lowering refuses. A compile that
passes is not a chip run. The topology is described inside a fixture, and
every test here compiles in this process (see the module docstring of
``chip_smoke.py`` for the one-process-per-chip rule).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as DA
from repro.kernels import dual_rmsnorm as DR
from repro.kernels import flash_attention as FA

SLOTS, HKV, GROUP, HD, PS, D = 8, 4, 8, 128, 16, 4096
PAGES_PER_SLOT = 64                       # 1024 tokens per slot
N_PAGES = 1 + SLOTS * PAGES_PER_SLOT      # + the garbage page
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plug-in in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent cache off: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"
    return compiled


@pytest.mark.parametrize("hkv", [HKV, 1], ids=["tp1", "tp4_rank"])
@pytest.mark.parametrize("pair", [False, True], ids=["single", "pair"])
def test_paged_decode_compiles(one_chip, pair, hkv):
    """tp=1 holds all 4 kv heads; a tp=4 rank holds one (kv sharded)."""
    lead = (2,) if pair else ()
    fn = DA.decode_attention_pair_paged if pair else DA.decode_attention_paged
    _compile(functools.partial(fn, interpret=False), one_chip,
             ((*lead, SLOTS, hkv, GROUP, HD), BF16),
             ((*lead, N_PAGES, hkv, PS, HD), BF16),
             ((*lead, N_PAGES, hkv, PS, HD), BF16),
             ((SLOTS, PAGES_PER_SLOT), jnp.int32),
             ((SLOTS,), jnp.int32))


def test_flash_prefill_compiles(one_chip):
    """Causal GQA-folded prefill: one 1024-token row per kv head, each q
    row of the fold is [position, group]."""
    S = 1024
    fn = functools.partial(FA.flash_attention, kind="causal", q_group=GROUP,
                           interpret=False)
    _compile(fn, one_chip, ((HKV, S * GROUP, HD), BF16),
             ((HKV, S, HD), BF16), ((HKV, S, HD), BF16))


@pytest.mark.parametrize("rows", [SLOTS, 4096], ids=["decode", "prefill"])
def test_dual_rmsnorm_compiles(one_chip, rows):
    fn = functools.partial(DR.dual_rmsnorm, interpret=False)
    _compile(fn, one_chip, ((rows, D), BF16), ((D,), BF16), ((D,), BF16))
