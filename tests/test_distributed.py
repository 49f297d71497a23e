"""Distributed-path parity: the sharded implementations (context-parallel
attention, flash-decoding, expert-parallel MoE, vocab-parallel embed/loss,
sharded train step) must equal their single-device references.

These run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps seeing 1 device (see tests/conftest.py).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parents[1] / "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.sharding import use_ctx
from repro.models.attention import (context_attention, decode_attention,
                                    naive_attention, decode_attention_local)
from repro.models import embedloss
from repro.models.moe import moe_apply, moe_dense_oracle
from repro.models.config import MoEConfig, get_smoke_config
from repro.models.transformer import Model

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
ok = []

# ---- context attention (train/prefill path) ----
B, S, Hq, Hkv, D = 2, 32, 6, 2, 16
q = jnp.asarray(rng.normal(size=(B,S,Hq,D)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B,S,Hkv,D)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B,S,Hkv,D)), jnp.float32)
ref = naive_attention(q, k, v, causal=True)
with use_ctx(mesh):
    out = jax.jit(lambda q,k,v: context_attention(q,k,v,causal=True))(q,k,v)
assert float(jnp.abs(out-ref).max()) < 1e-5, "context_attention"
ok.append("context_attention")

with use_ctx(mesh):
    outw = jax.jit(lambda q,k,v: context_attention(q,k,v,causal=True,window=8))(q,k,v)
refw = naive_attention(q, k, v, causal=True, window=8)
assert float(jnp.abs(outw-refw).max()) < 1e-5, "window context_attention"
ok.append("window_context_attention")

# ---- flash decoding (cache seq-sharded over model) ----
kc = jnp.asarray(rng.normal(size=(B, 32, Hkv * D)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B, 32, Hkv * D)), jnp.float32)
qd = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
pos = jnp.int32(19)
o_ref, _, _ = decode_attention_local(qd, kc, vc, pos=pos)
with use_ctx(mesh):
    o = jax.jit(lambda q,k,v,p: decode_attention(q,k,v,pos=p))(qd,kc,vc,pos)
assert float(jnp.abs(o - o_ref.reshape(B,Hq,D)).max()) < 1e-5, "decode_attention"
ok.append("decode_attention")

# ---- MoE: a2a (seq divisible) and psum (seq=1) vs dense oracle ----
cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=8.0)
Dm = 16
params = {
    "router": jnp.asarray(rng.normal(size=(Dm, 8)), jnp.float32),
    "w_gate": jnp.asarray(rng.normal(size=(8, Dm, 32))*0.1, jnp.float32),
    "w_up": jnp.asarray(rng.normal(size=(8, Dm, 32))*0.1, jnp.float32),
    "w_down": jnp.asarray(rng.normal(size=(8, 32, Dm))*0.1, jnp.float32),
}
x = jnp.asarray(rng.normal(size=(2, 8, Dm)), jnp.float32)
ref = moe_dense_oracle(x.reshape(-1, Dm), params, cfg).reshape(2, 8, Dm)
with use_ctx(mesh):
    a2a = jax.jit(lambda x: moe_apply(x, params, cfg))(x)
assert float(jnp.abs(a2a-ref).max()) < 1e-4, "moe a2a"
ok.append("moe_a2a")
x1 = x[:, :1]
ref1 = moe_dense_oracle(x1.reshape(-1, Dm), params, cfg).reshape(2, 1, Dm)
with use_ctx(mesh):
    ps = jax.jit(lambda x: moe_apply(x, params, cfg))(x1)
assert float(jnp.abs(ps-ref1).max()) < 1e-4, "moe psum"
ok.append("moe_psum")
# multi-axis experts (pod-style): experts over both mesh axes
with use_ctx(mesh, rules={"experts": ("data", "model"), "batch": ()}):
    ps2 = jax.jit(lambda x: moe_apply(x, params, cfg))(x1)
assert float(jnp.abs(ps2-ref1).max()) < 1e-4, "moe psum multi"
ok.append("moe_psum_multiaxis")

# ---- vocab-parallel embed + loss grads ----
V, Dm2 = 64, 16
table = jnp.asarray(rng.normal(size=(V, Dm2)), jnp.float32)
xx = jnp.asarray(rng.normal(size=(2, 8, Dm2)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 60, (2, 8)), jnp.int32)
def loss(x, t): return embedloss.lm_loss(x, t, labels, valid_vocab=60, seq_chunk=4)
with use_ctx(mesh):
    l1, g1 = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(xx, table)
with use_ctx(None):
    l2, g2 = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(xx, table)
assert abs(float(l1-l2)) < 1e-5 and float(jnp.abs(g1[1]-g2[1]).max()) < 1e-5, "lm_loss"
ok.append("lm_loss_grads")

# ---- whole-model loss parity: sharded vs local ----
for arch in ("stablelm-3b", "gemma3-1b", "kimi-k2-1t-a32b", "mamba2-1.3b",
             "zamba2-7b", "whisper-small", "internvl2-26b"):
    import dataclasses
    scfg = get_smoke_config(arch)
    if scfg.moe is not None:
        scfg = dataclasses.replace(scfg, moe=dataclasses.replace(
            scfg.moe, capacity_factor=float(scfg.moe.n_experts)))
    model = Model(scfg)
    p = model.init(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, scfg.vocab, (2, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, scfg.vocab, (2, 16)), jnp.int32)}
    if scfg.kind == "vlm":
        batch["patches"] = jnp.asarray(rng.normal(size=(2, scfg.n_patches, scfg.d_model)), jnp.float32)
    if scfg.kind in ("audio", "encdec"):
        batch["frames"] = jnp.asarray(rng.normal(size=(2, scfg.enc_len, scfg.d_model)), jnp.float32)
    with use_ctx(None):
        l_local = float(jax.jit(model.loss)(p, batch))
    with use_ctx(mesh):
        l_shard = float(jax.jit(model.loss)(p, batch))
    assert abs(l_local - l_shard) < 2e-3, (arch, l_local, l_shard)
    ok.append(f"model_loss:{arch}")

print("PASS", len(ok), "checks:", ",".join(ok))
"""


@pytest.mark.timeout(900)
def test_distributed_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=880)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    assert "PASS" in res.stdout
