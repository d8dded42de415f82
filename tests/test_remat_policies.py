"""Remat policies (engine remat_policy values) and their name-string
contract with the checkpoint_name anchors in models/llama.py — a rename on
either side would silently degrade save_only_these_names to full recompute,
so the coupling is pinned here (VERDICT r3 item 4 infrastructure)."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import ParallelEngine

#: every name an engine policy references must appear in the model jaxpr
ENGINE_POLICY_NAMES = {"attn_out", "qkv", "mlp_out"}


def _engine(policy):
    paddle.seed(0)
    cfg = llama_tiny_config(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=m.parameters())
    return ParallelEngine(m, optimizer=opt, loss_fn=m.loss_fn, remat=True,
                          remat_policy=policy, donate=False), cfg


def test_checkpoint_names_present_in_model_jaxpr():
    from paddle_tpu.jit import functional_call, state_values
    from paddle_tpu.framework.core import Tensor

    cfg = llama_tiny_config(use_flash_attention=False)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    params = state_values(m)
    ids = np.zeros((1, 8), np.int32)

    def fwd(p, x):
        return functional_call(m, p, Tensor(x)).value

    jaxpr = jax.make_jaxpr(fwd)(params, ids)
    text = str(jaxpr)
    for name in ENGINE_POLICY_NAMES:
        assert f"name={name}" in text or f"'{name}'" in text or \
            name in text, f"checkpoint_name {name!r} missing from model jaxpr"


@pytest.mark.parametrize("policy", ["dots", "nothing", "save_attn",
                                    "save_attn_mlp", "save_qkv_attn"])
def test_policy_trains_one_step(policy):
    eng, cfg = _engine(policy)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 32))
                           .astype("int32"))
    lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 32))
                           .astype("int64"))
    loss = float(np.asarray(eng.train_batch(ids, lbl).value))
    assert np.isfinite(loss), (policy, loss)


def test_unknown_policy_raises():
    eng, cfg = _engine("definitely_not_a_policy")
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 32))
                           .astype("int32"))
    lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 32))
                           .astype("int64"))
    with pytest.raises(ValueError, match="remat_policy"):
        eng.train_batch(ids, lbl)


def test_save_attn_actually_saves_fewer_residuals():
    """The named policy must change what is saved vs nothing_saveable —
    proves the names reach jax.checkpoint (a dead name would make both
    identical)."""
    import io
    from contextlib import redirect_stdout
    from jax.ad_checkpoint import print_saved_residuals

    cfg = llama_tiny_config(use_flash_attention=False)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    from paddle_tpu.jit import functional_call, state_values
    from paddle_tpu.framework.core import Tensor

    params = state_values(m)
    ids = np.zeros((2, 16), np.int32)
    lbl = np.zeros((2, 16), np.int64)

    def loss_of(p):
        out = functional_call(m, p, Tensor(ids))
        return m.loss_fn(out, Tensor(lbl)).value

    def saved(policy):
        f = jax.checkpoint(loss_of, policy=policy)
        buf = io.StringIO()
        with redirect_stdout(buf):
            print_saved_residuals(f, params)
        return buf.getvalue()

    cp = jax.checkpoint_policies
    with_names = saved(cp.save_only_these_names("attn_out", "mlp_out"))
    without = saved(cp.nothing_saveable)
    # the named policy saves the attention/MLP outputs (reported with their
    # llama.py source lines); nothing_saveable saves only arguments
    assert "LlamaAttention" in with_names and "LlamaMLP" in with_names, \
        with_names[-500:]
    assert "LlamaAttention" not in without and "LlamaMLP" not in without


def test_offload_opt_state_requires_pinned_host():
    """The CPU backend has no pinned_host memory (and no placement custom
    call) — the engine must say so clearly instead of failing mid-compile.
    The trains-and-stays-on-host behavior is verified ON CHIP
    (tests_tpu/: offload parity and grad-accum cases)."""
    paddle.seed(0)
    cfg = llama_tiny_config(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=m.parameters())
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    kinds = {mm.kind for mm in jax.devices()[0].addressable_memories()}
    if "pinned_host" in kinds:
        pytest.skip("TPU backend: covered by the on-chip benchmark")
    with pytest.raises(NotImplementedError, match="pinned_host"):
        ParallelEngine(m, optimizer=opt, loss_fn=m.loss_fn, mesh=mesh,
                       offload_opt_state=True)


def test_offload_multi_device_raises():
    from jax.sharding import Mesh

    cfg = llama_tiny_config(use_flash_attention=False)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=m.parameters())
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("data",))
    with pytest.raises(NotImplementedError):
        ParallelEngine(m, optimizer=opt, loss_fn=m.loss_fn, mesh=mesh,
                       offload_opt_state=True)
