"""Bring-up guards (PR 21): what used to hide that the chip path was broken.

* each op's selection rule (ops/select.py) answers as documented for
  (platform, partitioning, shape) — asked with the platform as an argument,
  so the TPU answers are checked from this CPU host;
* a selected kernel that raises reaches the caller (no call site continues
  on another path);
* every kernel the rules can select at the chip_smoke / tests_tpu shapes
  compiles against the ``v5e:2x2`` compile-only target, in a child process
  that pins the platform with ``jax.config`` and leaves ``JAX_PLATFORMS``
  unset (under ``JAX_PLATFORMS=cpu`` paddle_tpu enables x64 + "highest"
  matmuls, and Mosaic then rejects kernels that are fine on the chip);
* the peaks table never guesses, the compile cache lands where documented,
  ``fsdp`` shards the parameters that carry a pspec, a ragged batch and
  ``--nproc_per_node > 1`` on a TPU host are errors, data-loader children
  are pinned to the CPU.
"""
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from paddle_tpu.ops import select

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # the kernel mode is process-wide and servers built by earlier test
    # files may have left it pinned (GenerationServer(kernels=...))
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    env.update(extra)
    return env


# ------------------------------------------------------------------ rules
class TestSelectionRules:
    Q, K = (8, 16, 2048, 128), (8, 8, 2048, 128)

    def test_platform_and_mode_decide_the_backend(self):
        assert select.pallas_backend("tpu") == select.PALLAS
        assert select.pallas_backend("cpu") is None
        select.set_kernel_mode("pallas")
        try:
            assert select.pallas_backend("cpu") == select.INTERPRET
            assert select.pallas_backend("tpu") == select.PALLAS
            select.set_kernel_mode("reference")
            assert select.pallas_backend("tpu") is None
        finally:
            select.set_kernel_mode("auto")

    def test_flash(self):
        rule = select.select_flash_attention
        assert rule(self.Q, self.K, platform="tpu",
                    is_partitioned=False) == "pallas"
        assert rule(self.Q, self.K, platform="cpu",
                    is_partitioned=False) == "xla"
        # Mosaic kernels cannot be partitioned automatically
        assert rule(self.Q, self.K, platform="tpu",
                    is_partitioned=True) == "xla"
        # the backward kernels are refused below / off multiples of 128
        for s in (16, 40, 100, 1000):
            assert rule((2, 4, s, 128), (2, 2, s, 128), platform="tpu",
                        is_partitioned=False) == "xla", s
        assert rule((2, 4, 512, 32), (2, 2, 512, 32), platform="tpu",
                    is_partitioned=False) == "xla"
        assert rule((2, 4, 512, 64), (2, 2, 512, 64), platform="tpu",
                    is_partitioned=False) == "pallas"

    def test_fused_norm_and_its_row_tile(self):
        rule = select.select_fused_norm
        kw = dict(platform="tpu", is_partitioned=False)
        assert rule(8192, 4096, jnp.bfloat16, **kw) == "pallas"
        assert rule(8192, 4096, jnp.bfloat16, platform="tpu",
                    is_partitioned=True) == "xla"
        assert rule(8192, 4096, jnp.bfloat16, platform="cpu",
                    is_partitioned=False) == "xla"
        assert rule(64, 100, jnp.float32, **kw) == "xla"      # lanes
        assert rule(520, 4096, jnp.bfloat16, **kw) == "xla"   # no tile
        # the tile comes from width, dtype and the VMEM budget: the fixed
        # 512 was refused at width 4096 (16.01-16.21 MiB scoped VMEM)
        tile = select.norm_block_rows
        assert tile(16384, 2048, jnp.bfloat16) == 512
        assert tile(8192, 4096, jnp.bfloat16) == 256
        assert tile(8192, 4096, jnp.float32) == 128
        assert tile(8, 4096, jnp.bfloat16) == 8               # decode rows
        assert tile(8192, 4096, jnp.bfloat16, want=64) == 64  # swept, lower
        assert tile(8192, 4096, jnp.bfloat16, want=4096) == 256

    def test_paged_attention(self):
        rule = select.select_paged_attention
        pool = (512, 16, 8, 128)
        kw = dict(platform="tpu", is_partitioned=False)
        for w in (1, 4, 128):          # decode, verify, prefill chunk
            assert rule((8, w, 32, 128), pool, **kw) == "pallas", w
        # the tp serving executor is a GSPMD program: jnp there
        assert rule((8, 1, 32, 128), pool, platform="tpu",
                    is_partitioned=True) == "xla"
        assert rule((8, 1, 32, 128), pool, platform="cpu",
                    is_partitioned=False) == "xla"
        assert rule((8, 1, 8, 64), (512, 16, 4, 64), **kw) == "xla"
        assert rule((8, 1, 32, 128), (512, 12, 8, 128), **kw) == "xla"
        # accumulator past the VMEM budget (512-token prefill chunk)
        assert rule((1, 512, 32, 128), pool, **kw) == "xla"

    def test_lora_w8_and_adamw(self):
        kw = dict(platform="tpu", is_partitioned=False)
        lora = select.select_lora_matmul
        bf = jnp.bfloat16
        assert lora((8, 1, 4096), (4096, 4096), 16, bf, bf, **kw) == "pallas"
        assert lora((8, 1, 4096), (4096, 14336), 16, bf, bf,
                    **kw) == "pallas"
        # down_proj at FFN 14336: the lane-padded A factor alone is 14.7 MB
        assert lora((8, 1, 14336), (14336, 4096), 16, bf, bf, **kw) == "xla"
        assert lora((8, 1, 4096), (4096, 4096), 16, bf, bf, platform="tpu",
                    is_partitioned=True) == "xla"
        w8 = select.select_w8_matmul
        assert w8(8, 4096, 14336, **kw) == "pallas"
        assert w8(32, 4096, 14336, **kw) == "xla"     # prefill: M > 16
        assert w8(8, 96, 128, **kw) == "xla"
        adamw = select.select_fused_adamw
        assert adamw((256, 1024), platform="tpu", n_devices=1) == "pallas"
        assert adamw((256, 1024), platform="tpu", n_devices=4) == "xla"
        assert adamw((256, 1000), platform="tpu", n_devices=1) == "xla"

    def test_partitioned_sees_gspmd_but_not_a_manual_island(self):
        from paddle_tpu.parallel import mesh_context

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "tensor"))
        seen = {}

        def inner(x):
            seen["island"] = select.partitioned()
            return x

        def outer(x):
            with mesh_context(mesh):
                seen["gspmd"] = select.partitioned()
                return jax.shard_map(inner, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"))(x)

        assert not select.partitioned()
        jax.jit(outer)(jnp.ones((4, 4)))
        assert seen == {"gspmd": True, "island": False}

    def test_counter_records_what_was_traced(self):
        from paddle_tpu.ops.fused_norm import fused_rms_norm

        select.selected(reset=True)
        fused_rms_norm(jnp.ones((8, 128)), jnp.ones((128,)))
        assert select.selected(reset=True) == {"fused_norm": {"xla": 1}}
        assert select.selected() == {}


# ------------------------------------------------- no hidden fallbacks
class _Boom(RuntimeError):
    pass


def _boom(*a, **k):
    raise _Boom("kernel made to fail")


class TestSelectedKernelFailureReachesTheCaller:
    """Every site that used to ``except Exception: pass`` (or ``except
    NotImplementedError: return None``) around its kernel call."""

    def test_flash_forward_and_vjp(self, monkeypatch):
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        q = jnp.ones((1, 2, 128, 64), jnp.float32)
        monkeypatch.setattr(fa, "_flash_fwd_bhsd", _boom)
        with pytest.raises(_Boom):
            fa.flash_attention(q, q, q, True)
        with pytest.raises(_Boom):
            jax.grad(lambda a: fa.flash_attention(a, q, q, True).sum())(q)

    def test_flash_backward(self, monkeypatch):
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        q = jnp.ones((1, 2, 128, 64), jnp.float32)
        monkeypatch.setattr(fa, "_flash_bwd_bhsd", _boom)
        with pytest.raises(_Boom):
            jax.grad(lambda a: fa.flash_attention(a, q, q, True).sum())(q)

    def test_sdpa(self, monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.nn.functional import scaled_dot_product_attention
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        monkeypatch.setattr(fa, "_flash_fwd_bhsd", _boom)
        x = paddle.to_tensor(np.ones((1, 128, 2, 64), "float32"))
        with pytest.raises(_Boom):
            scaled_dot_product_attention(x, x, x, is_causal=True)

    def test_fused_norms(self, monkeypatch):
        from paddle_tpu.ops import fused_norm as fn

        monkeypatch.setattr(fn, "_rms_pallas", _boom)
        monkeypatch.setattr(fn, "_ln_pallas", _boom)
        x, w = jnp.ones((64, 256)), jnp.ones((256,))
        with select.target_platform("tpu"):
            with pytest.raises(_Boom):
                fn.fused_rms_norm(x, w)
            with pytest.raises(_Boom):
                fn.fused_layer_norm(x, w, w)

    def test_paged_attention_fp_and_int8(self, monkeypatch):
        from paddle_tpu.ops import paged_attention as pa
        from paddle_tpu.ops import paged_attention_pallas as pk

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        monkeypatch.setattr(pk, "_paged_attention_call", _boom)
        q = jnp.ones((2, 1, 4, 32), jnp.float32)
        pool = jnp.ones((8, 4, 2, 32), jnp.float32)
        tables = jnp.zeros((2, 2), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        with pytest.raises(_Boom):
            pa.paged_decode_attention(q, pool, pool, tables, pos)
        kq, ks = pa.quantize_block_kv(pool)
        with pytest.raises(_Boom):
            pa.paged_decode_attention_q(q, kq, ks, kq, ks, tables, pos)
        with pytest.raises(_Boom):
            pa.paged_prefill_attention(q[:1].reshape(1, 1, 4, 32), pool, pool,
                                       tables[0], 0)

    def test_w8_matmul(self, monkeypatch):
        from paddle_tpu.ops import int8

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        monkeypatch.setattr(int8, "_w8_matmul_pallas", _boom)
        wq, sc = int8.quantize_per_channel(jnp.ones((128, 128)))
        with pytest.raises(_Boom):
            int8.w8_matmul(jnp.ones((8, 128)), wq, sc)

    def test_lora_matmul(self, monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.nn.lora import lora_matmul
        from paddle_tpu.ops import paged_attention_pallas as pk

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        monkeypatch.setattr(pk, "fused_lora_matmul", _boom)
        x = paddle.to_tensor(np.ones((2, 1, 128), "float32"))
        w = paddle.to_tensor(np.ones((128, 128), "float32"))
        ab = (jnp.ones((2, 128, 4)), jnp.ones((2, 4, 128)), jnp.ones((2,)))
        with pytest.raises(_Boom):
            lora_matmul(x, w, ab)

    def test_fused_adamw(self, monkeypatch):
        from paddle_tpu.ops import fused_adamw as fa

        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        monkeypatch.setenv("PT_FUSED_ADAMW", "1")
        monkeypatch.setattr(fa, "_fused_call", _boom)
        p = jnp.ones((64, 256))
        hp = dict(lr=1e-3, step=1, b1=0.9, b2=0.999, eps=1e-8, decay=0.0)
        with pytest.raises(_Boom):
            fa.fused_adamw_update(p, p, p, p, **hp)
        with pytest.raises(_Boom):
            fa.flat_adamw_update(p, p, p, p, **hp)

    def test_device_platform_does_not_answer_cpu_for_a_dead_backend(
            self, monkeypatch):
        from paddle_tpu import device

        def dead():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(device.jax, "devices", dead)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            device.get_device()


# --------------------------------------------------- compile-only target
def test_selected_kernels_compile_for_the_v5e_target():
    """Runs XLA:TPU and Mosaic for real against a compile-only ``v5e:2x2``
    topology — the check that would have caught the refused norm tile, the
    paged-attention block shape and the LoRA scale block before any chip."""
    # every kernel family at the serving/training widths; the two
    # multi-second flash cases (509M train batch, S=16384 streaming grid)
    # stay with the full `tools/compile_check.py` run
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "compile_check.py"),
         "--only", "flash.gqa,flash.head_dim,norm,paged,ssm,lora,w8,latent"],
        env=_clean_env(), capture_output=True, text=True, timeout=600)
    tail = out.stdout[-3000:] + out.stderr[-2000:]
    assert out.returncode == 0, tail
    assert "all cases compile" in out.stdout, tail
    assert "device_kind='TPU v5 lite'" in out.stdout, tail


def test_compile_check_exempts_no_case():
    """Every case ``tools/compile_check.py`` lists must compile: a case that
    raises fails the run whatever its name — there is no list of refusals
    the tool forgives (the one it had left with the kernel it was kept for,
    PR 31). In a child: importing the tool re-pins the platform."""
    code = (
        "import json, compile_check as cc\n"
        "def boom(): raise RuntimeError('refused')\n"
        "names = [c[0] for c in cc.kernel_cases() + cc.program_cases(None)]\n"
        "failed = cc.run_cases([(n, boom) for n in names], None)\n"
        "print(json.dumps({'names': names, 'failed': failed,\n"
        "                  'exempt': hasattr(cc, 'KNOWN_' 'REFUSALS')}))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=_clean_env(
            PYTHONPATH=os.path.join(REPO, "tools") + os.pathsep + REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(got["names"]) > 20 and got["failed"] == got["names"]
    assert not got["exempt"]


@pytest.mark.parametrize("mode", ["auto", "pallas", "reference"])
def test_each_serving_program_has_its_one_body(mode):
    """No kernel mode swaps a program's body: the executor jits
    ``_decode_paged_fn`` and ONE chunk program themselves — the joint
    ``_decode_chunk_fn`` where a chunk can ride in the decode trip's call,
    ``_chunk_prefill_fn`` elsewhere (here: a ``tick_window`` scan) — and
    holds one ``*_fn`` body per program and no twin."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.executor import PagedExecutor
    from paddle_tpu.inference.serving import GenerationServer
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=64, dtype="float32",
        use_flash_attention=False))

    def executor(**kw):
        return GenerationServer(model, max_len=32, cache="paged",
                                block_size=4, kernels=mode, **kw)._exec

    ex = executor()
    assert ex.decode_paged.__wrapped__.__func__ \
        is PagedExecutor._decode_paged_fn
    assert ex.chunk_alone_why is None and ex.chunk_prefill is None
    assert ex.decode_chunk.__wrapped__.__func__ \
        is PagedExecutor._decode_chunk_fn
    ex = executor(tick_window=2)
    assert ex.chunk_alone_why == "tick_window" and ex.decode_chunk is None
    assert ex.chunk_prefill.__wrapped__.__func__ \
        is PagedExecutor._chunk_prefill_fn
    assert sorted(n for n in vars(PagedExecutor) if n.endswith("_fn")) == [
        "_chunk_prefill_fn", "_decode_chunk_fn", "_decode_paged_fn",
        "_spec_scan_fn", "_spec_verify_fn"]


# ------------------------------------------------------------ peaks, cache
def test_peaks_table_raises_on_an_unknown_device_kind():
    from paddle_tpu.utils import bench_timing as bt

    assert bt.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        bt.peak_flops("TPU v9")
    with pytest.raises(KeyError):
        bt.peak_hbm_bandwidth("TPU v9")


def _cache_dir_in_child(env):
    # load the helper by path: a fresh interpreter per case (jax.config is
    # process-wide) without paying the whole paddle_tpu import each time
    code = ("import importlib.util, json, jax; "
            "spec = importlib.util.spec_from_file_location('cc', %r); "
            "cc = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(cc); d = cc.enable_compile_cache(); "
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))"
            % os.path.join(REPO, "paddle_tpu", "utils", "compile_cache.py"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_honours_the_variable_or_lands_in_the_checkout(
        tmp_path):
    # variable set: JAX reads it, the helper sets no other directory
    got, configured = _cache_dir_in_child(
        _clean_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert got == str(tmp_path) and configured == str(tmp_path)
    # unset: <checkout>/.jax_cache, computed from __file__
    got, configured = _cache_dir_in_child(_clean_env())
    assert got == configured == os.path.join(REPO, ".jax_cache")
    # a CPU-pinned process (this one) gets no cache at all
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ------------------------------------------------------------- four chips
class TestEngineLayouts:
    def _engine(self, mesh, **kw):
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
        from paddle_tpu.optimizer import AdamW
        from paddle_tpu.parallel import ParallelEngine

        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny_config(use_flash_attention=False))
        opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
        return ParallelEngine(model, optimizer=opt, loss_fn=None, mesh=mesh,
                              **kw)

    def test_fsdp_shards_the_parameters_that_carry_a_pspec(self):
        from paddle_tpu.parallel.engine import _add_fsdp_axis

        assert _add_fsdp_axis(P(None, "tensor"), (4096, 14336), 4,
                              "sharding") == P("sharding", "tensor")
        assert _add_fsdp_axis(P("tensor", None), (14336, 4096), 4,
                              "sharding") == P("tensor", "sharding")
        assert _add_fsdp_axis(P(), (4096,), 4, "sharding") == P("sharding")
        assert _add_fsdp_axis(P(), (64,), 4, "sharding") == P()     # tiny
        assert _add_fsdp_axis(P(None, None), (4097, 33), 4,
                              "sharding") == P(None, None)  # nothing divides

        mesh = Mesh(np.array(jax.devices()[:4]), ("sharding",))
        eng = self._engine(mesh, fsdp=True)
        total = per_dev = 0          # per_dev: the first device's share
        for arr in jax.tree_util.tree_leaves([eng.params, eng.opt_state]):
            total += arr.nbytes
            per_dev += sum(s.data.nbytes for s in arr.addressable_shards
                           if s.device == mesh.devices.flat[0])
        # every large Llama parameter has a pspec; before PR 21 fsdp only
        # touched parameters WITHOUT one and left ~all bytes replicated
        assert per_dev <= 1.3 * total / 4, (per_dev, total)
        assert "sharding" in eng.specs["model.layers.0.mlp.up_proj.weight"]

    def test_a_ragged_batch_is_an_error_not_a_silent_replication(self):
        import paddle_tpu as paddle

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        eng = self._engine(mesh)
        ids = paddle.to_tensor(np.ones((6, 16), "int32"))
        lbl = paddle.to_tensor(np.ones((6, 16), "int64"))
        with pytest.raises(ValueError, match="does not split evenly"):
            eng.train_batch(ids, lbl)


# ------------------------------------------------------ one process per chip
def test_launcher_refuses_several_ranks_on_a_tpu_host(monkeypatch, capsys):
    from paddle_tpu.distributed.launch import main as launch

    monkeypatch.setattr(launch, "_ranks_would_use_tpu", lambda: True)
    assert launch.launch(["--nproc_per_node", "2", "train.py"]) == 2
    assert "one process at a time" in capsys.readouterr().err
    # the detector never imports jax, and a CPU pin clears it
    monkeypatch.undo()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch._ranks_would_use_tpu() is False


class _EnvDataset:
    def __len__(self):
        return 4

    def __getitem__(self, i):
        return np.array([os.environ.get("JAX_PLATFORMS") == "cpu"])


def test_dataloader_children_are_pinned_to_the_cpu(monkeypatch):
    from paddle_tpu.io import DataLoader

    # the parent claims a chip; its data-loader children must not
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    seen = [np.asarray(b[0] if isinstance(b, (list, tuple)) else b)
            for b in DataLoader(_EnvDataset(), batch_size=2, num_workers=2)]
    assert seen and all(bool(np.all(x)) for x in seen)
