"""Compile the served path's kernels and step for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler builds each program for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (misaligned blocks, VMEM overflow, a step that does
not fit in HBM). Interpret-mode tests cannot see those faults.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.chunked import chunked_attention_tpu
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.ssd_scan.kernel import ssd_tpu
from repro.models.config import get_config
from repro.models.transformer import Model

# one v5e chip has 16 GiB of HBM; leave room for what the process holds
# besides the step program
DECODE_STEP_BUDGET_BYTES = 14e9
# the served step updates its cache in place: what it needs besides its
# arguments is a few activations, not a copy of the cache
SERVED_STEP_TEMP_BYTES = 64 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    """Compile ``fn`` for the described chip; the Pallas kernel must be in
    the program as a TPU custom call, not lowered to something else."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_stablelm_widths(one_chip):
    # stablelm-3b: 32 q heads = 32 kv heads, head_dim 80, 2k prefill
    q = _spec((1, 32, 2048, 80), jnp.bfloat16, one_chip)
    _compile_kernel(
        lambda q, k, v: flash_attention_tpu(q, k, v, causal=True), q, q, q)


def test_ssd_mamba2_widths(one_chip):
    # mamba2-1.3b: d_inner 4096 / head_dim 64 = 64 heads, d_state 128
    b, l, h, p, n = 1, 2048, 64, 64, 128
    x = _spec((b, l, h, p), jnp.bfloat16, one_chip)
    dt = _spec((b, l, h), jnp.float32, one_chip)
    a = _spec((h,), jnp.float32, one_chip)
    bc = _spec((b, l, n), jnp.bfloat16, one_chip)
    _compile_kernel(
        lambda x, dt, a, bm, cm: ssd_tpu(x, dt, a, bm, cm, chunk=256),
        x, dt, a, bc, bc)


def test_chunked_attention_long_context(one_chip):
    # K/V stream through VMEM in tiles, so a 32k context fits; the kernel
    # used to hold the whole K/V sequence per grid step and ran out of VMEM
    q = _spec((1, 32, 32768, 80), jnp.bfloat16, one_chip)
    _compile_kernel(
        lambda q, k, v: chunked_attention_tpu(q, k, v, causal=True), q, q, q)


def test_stablelm_decode_step_fits_one_chip(one_chip):
    """The served step at full width, 8 slots x 1024 positions, fits in
    one chip's HBM with room to spare."""
    model = Model(get_config("stablelm-3b"))
    place = lambda s: _spec(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(place, model.abstract_params())
    cache = jax.tree.map(place, model.init_cache(8, 1024, abstract=True))
    tokens = _spec((8,), jnp.int32, one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < DECODE_STEP_BUDGET_BYTES, (
        f"decode step needs {used / 1e9:.2f} GB "
        f"(args {mem.argument_size_in_bytes / 1e9:.2f} GB, "
        f"temps {mem.temp_size_in_bytes / 1e9:.2f} GB)")


def _cache_moves(hlo: str, leaf, *, layer_writes: bool = False) -> list[str]:
    """HLO instructions, fused ones included, that move a whole cache leaf
    or one layer of it: a ``copy`` of that shape, or a
    ``dynamic-update-slice`` whose update has it. A one-layer shape is
    counted with and without its layer axis. A dynamic-update-slice that
    writes a token or a lane into the cache in place is not a move; with
    ``layer_writes`` neither is one that writes one layer in place (an SSM
    state, which every lane changes each step)."""
    dims = [leaf.shape, (1,) + leaf.shape[1:], leaf.shape[1:]]
    dt = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(leaf.dtype).name]
    moved = {f"{dt}[{','.join(map(str, d))}]" for d in dims}
    written = {f"{dt}[{','.join(map(str, leaf.shape))}]"} if layer_writes \
        else moved
    inst = re.compile(r"%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(%([^,)\s]+)"
                      r"(?:, %([^,)\s]+))?")
    found = [m for m in map(inst.search, hlo.splitlines()) if m]
    shape = {m[1]: m[2] for m in found}
    return [f"{m[3]} {m[1]}" for m in found
            if m[3] == "copy" and m[2] in moved
            or m[3] == "dynamic-update-slice" and shape.get(m[5]) in written]


def _served_step(arch, slots, sharding):
    """(cache, step, lane reset) for ``arch`` at full width, ``slots`` x
    1024 positions, compiled as the engine jits them: cache donated."""
    model = Model(get_config(arch))
    place = lambda s: _spec(s.shape, s.dtype, sharding)  # noqa: E731
    params = jax.tree.map(place, model.abstract_params())
    cache = jax.tree.map(place, model.init_cache(slots, 1024, abstract=True))
    step = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, _spec((slots,), jnp.int32, sharding)).compile()
    reset_lane = jax.jit(model.reset_cache_lane, donate_argnums=(0,)).lower(
        cache, _spec((), jnp.int32, sharding)).compile()
    return cache, step, reset_lane


def test_stablelm_served_step_updates_cache_in_place(one_chip):
    """The step and the lane reset as the engine jits them, with the cache
    in the device's default layout: the cache stays one buffer that each
    layer writes a token into, neither copied nor relaid out."""
    cache, step, reset_lane = _served_step("stablelm-3b", 8, one_chip)
    mem = step.memory_analysis()
    assert mem.temp_size_in_bytes < SERVED_STEP_TEMP_BYTES, (
        f"served step temporaries {mem.temp_size_in_bytes} B")
    assert step.output_formats[1] == step.input_formats[0][1]
    for name, program in (("step", step), ("reset_cache_lane", reset_lane)):
        hlo = program.as_text()
        for key in ("k", "v"):
            moves = _cache_moves(hlo, cache[key])
            assert not moves, f"{name} moves cache {key!r}: {moves}"


def _assert_state_in_place(cache, step, reset_lane):
    """The conv windows and float32 states stay one buffer each that every
    layer updates at its own index: neither copied whole nor a layer
    copied out; what the step needs besides its arguments is under one
    layer's state and a few activations."""
    mem = step.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < DECODE_STEP_BUDGET_BYTES
    state = cache["state"]
    layer_bytes = state.size // state.shape[0] * state.dtype.itemsize
    assert mem.temp_size_in_bytes < layer_bytes + SERVED_STEP_TEMP_BYTES, (
        f"served step temporaries {mem.temp_size_in_bytes} B")
    assert step.output_formats[1] == step.input_formats[0][1]
    for name, program in (("step", step), ("reset_cache_lane", reset_lane)):
        hlo = program.as_text()
        for key in ("conv", "state"):
            moves = _cache_moves(hlo, cache[key], layer_writes=True)
            assert not moves, f"{name} moves cache {key!r}: {moves}"


def test_mamba2_served_step_updates_state_in_place(one_chip):
    """mamba2-1.3b's step and lane reset as the engine jits them, at 32
    slots: the 3.2 GB of float32 states are read and written in place, a
    layer at a time, with no second copy of them."""
    _assert_state_in_place(*_served_step("mamba2-1.3b", 32, one_chip))


def test_granite_served_step_keeps_kv_in_place(one_chip):
    """granite-4.0-h-micro's step and lane reset as the engine jits them,
    at 32 slots x 1024 positions: the step fits one chip; its two KV
    stacks stay one buffer each that the attention applications write a
    token into, neither copied nor relaid out; its Mamba2 conv windows and
    float32 states are updated in place, a layer at a time, so what it
    needs besides its arguments is under one layer's state and a few
    activations."""
    cache, step, reset_lane = _served_step("granite-4.0-h-micro", 32,
                                           one_chip)
    _assert_state_in_place(cache, step, reset_lane)
    for name, program in (("step", step), ("reset_cache_lane", reset_lane)):
        hlo = program.as_text()
        for key in ("k", "v"):
            moves = _cache_moves(hlo, cache[key])
            assert not moves, f"{name} moves cache {key!r}: {moves}"
