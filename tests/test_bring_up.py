"""Bring-up rules (PR 21): nothing on the main path may hide the device.

No model compiles here; the one slow case is the spawned DataLoader
worker (a fresh interpreter importing jax).
"""
import json
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import _compile_cache, insight, runtime
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- where the compile cache lives -----------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them, so no
    test arms a persistent cache for the rest of the suite."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(_compile_cache, "_install_listeners", lambda: None)
    return calls


def test_cache_placed_from_outside_sets_no_directory(
        monkeypatch, tmp_path, config_updates):
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    mx.config.set("compilation_cache_dir", str(tmp_path / "knob"))
    try:
        assert _compile_cache.configure(str(tmp_path / "arg")) == outside
    finally:
        mx.config.set("compilation_cache_dir", "")
    names = [n for n, _ in config_updates]
    assert "jax_compilation_cache_dir" not in names
    # only the two persistence thresholds are applied
    assert sorted(names) == ["jax_persistent_cache_min_compile_time_secs",
                             "jax_persistent_cache_min_entry_size_bytes"]
    assert not os.path.exists(outside)      # JAX's to create, not ours
    from mxnet_tpu.autotune import persist
    assert persist.cache_dir() == outside   # "next to the XLA cache"


def test_cache_unset_uses_the_fixed_checkout_path(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(_REPO, ".jax_cache")
    assert _compile_cache.CHECKOUT_CACHE == fixed
    assert _compile_cache.cache_dir(fixed) == fixed
    # package import alone arms nothing
    assert _compile_cache.configure() is None and config_updates == []
    target = str(tmp_path / "fixed")
    assert _compile_cache.configure(target) == target
    assert ("jax_compilation_cache_dir", target) in config_updates
    assert os.path.isdir(target)


# --- a kernel that fails must be heard -------------------------------------

def test_attention_reraises_a_kernel_failure_on_tpu(monkeypatch):
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import flash_attention as flash_mod

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(flash_mod, "flash_attention", refuse)
    seq = attention._FLASH_MIN_SEQ_CAUSAL
    q = mx.np.zeros((1, seq, 8), dtype="float32")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        attention.multi_head_attention(q, q, q, heads=1, causal=True)
    # below the threshold the kernel is not eligible: a condition, tested,
    # not an exception swallowed
    short = mx.np.zeros((1, 8, 8), dtype="float32")
    out = attention.multi_head_attention(short, short, short, heads=1,
                                         causal=True)
    assert out.shape == (1, 8, 8)


def test_flash_kernel_is_shard_mapped_under_a_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel (the four-chip run of PR 21
    stopped on it), so under a mesh scope each device must get its own
    (batch/dp, heads/tp) block through a shard_map."""
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import flash_attention as flash_mod
    from mxnet_tpu.parallel import MeshConfig
    from mxnet_tpu.parallel.mesh import activation_sharding
    seen = []

    def per_device(q, k, v, causal=False, window=None):
        seen.append(q.shape)
        b, h, s, d = q.shape

        def fold(t):
            return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        out = attention._reference_attention(fold(q), fold(k), fold(v), h,
                                             causal=causal)
        return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(flash_mod, "flash_attention", per_device)
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ_CAUSAL", 8)
    x = mx.np.array(onp.random.RandomState(0).randn(4, 8, 32)
                    .astype("float32"))
    want = attention.multi_head_attention(x, x, x, heads=4, causal=True)
    assert seen[-1] == (4, 4, 8, 8)             # no mesh: the whole array
    mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
    with activation_sharding(mesh):
        got = attention.multi_head_attention(x, x, x, heads=4, causal=True)
    assert seen[-1] == (2, 2, 8, 8)             # batch / dp, heads / tp
    onp.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-5,
                                atol=1e-6)


def test_interpret_mode_follows_the_one_platform_helper(monkeypatch):
    assert runtime.on_tpu() is False and runtime.pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.on_tpu() is True and runtime.pallas_interpret() is False


# --- no peak is assumed ----------------------------------------------------

def test_peak_lookup_raises_on_an_unknown_tpu():
    assert insight.peaks("cpu") == (1e11, 1e11, 5e10)
    assert insight.peaks("TPU v5 lite") == (197e12, 393e12, 819e9)
    with pytest.raises(MXNetError, match="no peak figures"):
        insight.peaks("TPU v9 ultra")
    from mxnet_tpu.autotune import kernels
    assert kernels._device_family("TPU v5 lite") == "v5e"
    assert kernels._device_family("TPU v5") == "v6"      # v5p's own name
    with pytest.raises(MXNetError, match="no static kernel blocks"):
        kernels._device_family("TPU v9 ultra")


# --- one process per chip --------------------------------------------------

class _PlatformProbe:
    """Each sample is [the worker's back-end is the CPU, its environment
    says so too]."""

    def __len__(self):
        return 2

    def __getitem__(self, i):
        return onp.array([jax.default_backend() == "cpu",
                          os.environ.get("JAX_PLATFORMS") == "cpu"],
                         "float32")


def test_spawned_dataloader_worker_is_pinned_to_the_cpu(monkeypatch):
    from mxnet_tpu.gluon.data import DataLoader
    # whatever the parent's environment says: with libtpu installed and
    # no chip, an unpinned worker would die reaching for the TPU
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    dl = DataLoader(_PlatformProbe(), batch_size=2, num_workers=1,
                    thread_pool=False)
    try:
        (batch,) = list(dl)
    finally:
        dl.close()
    assert batch.asnumpy().tolist() == [[1.0, 1.0], [1.0, 1.0]]


# --- the entry scripts refuse to pretend -----------------------------------

def test_chip_smoke_refuses_a_non_tpu_platform(capsys):
    import chip_smoke
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""        # no result line


def test_chip_smoke_last_line_is_the_verdict_alone(monkeypatch, capsys):
    # the driver parses the last stdout line strictly: exactly "ok" and
    # "device", the device exactly platform/kind/count (the first
    # submission of PR 21 was refused for carrying the report there)
    import types

    import chip_smoke
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    monkeypatch.setattr(chip_smoke, "run", lambda cfg: {"train": {}})
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().split("\n")
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert set(json.loads(lines[-2])) == {"report"}


def test_bench_needs_a_chip_or_an_explicit_cpu_request(monkeypatch):
    import bench
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._device().platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no accelerator"):
        bench._device()


def test_bench_exits_nonzero_when_a_row_raises(monkeypatch, capsys):
    import bench
    from mxnet_tpu import goodput

    def fine(on_cpu, peak, precision):
        return {"name": "fine", "items_per_s": 1.0, "precision": precision}

    def broken(on_cpu, peak, precision):
        raise RuntimeError("row blew up")

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(bench, "_GRID", [(fine, dict(precision="fp32")),
                                         (broken, dict(precision="fp32")),
                                         (fine, dict(precision="bf16"))])
    try:
        with pytest.raises(RuntimeError, match="row blew up"):
            bench.main([])
    finally:
        goodput.disable()
    doc = json.loads(capsys.readouterr().out.strip().rsplit("\n", 1)[-1])
    assert doc["platform"] == "cpu" and doc["device_count"] >= 1
    assert [r["name"] for r in doc["grid"]][0] == "fine"
    assert "row blew up" in doc["grid"][1]["error"]
    assert len(doc["grid"]) == 2                # the run ends at the error
