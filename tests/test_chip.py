"""Device selection without a fallback that hides the device
(mxnet_tpu/context.py) and the one compile-cache helper
(mxnet_tpu/chip.py)."""

import os

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chip, context
from mxnet_tpu.base import MXNetError


def test_accelerator_context_raises_without_accelerator(monkeypatch):
    """tpu(0) maps onto the host only under the explicit CPU pin (the
    tests' stand-in); in a process that was not pinned and found no
    accelerator it raises, naming the platform jax found."""
    assert context.tpu(0).jax_device.platform == "cpu"   # pinned: conftest
    monkeypatch.setattr(context, "_cpu_pinned", lambda: False)
    with pytest.raises(MXNetError, match="no accelerator.*'cpu'"):
        context.tpu(0).jax_device
    with pytest.raises(MXNetError, match="no accelerator"):
        context.gpu(0).jax_device
    # the default context is unaffected: no accelerator -> cpu(0)
    assert context._default_context() == context.cpu(0)


def test_context_index_out_of_range_raises():
    """No modulo wrap: tpu(n) on n devices is an error, not device 0."""
    n = len(jax.devices())
    assert context.tpu(n - 1).jax_device == jax.devices()[n - 1]
    for ctx in (context.tpu(n), context.cpu(n), context.gpu(n + 3)):
        with pytest.raises(MXNetError, match="out of range"):
            ctx.jax_device
    with pytest.raises(MXNetError, match="out of range"):
        mx.nd.zeros((2,), ctx=context.tpu(n))


@pytest.fixture
def _cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           _cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the helper reports it and sets no
    directory in code (jax reads the variable itself)."""
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch,
                                                      _cache_config):
    """Unset: <checkout>/.jax_cache, the same path on every call (the
    path is part of the cache key: no pid, time or temp name in it)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert chip.use_compile_cache() == want
    assert chip.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_peaks_keyed_by_device_kind():
    v5e = chip.peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert chip.peaks() == v5e        # CPU-pinned: the modelled kind
    with pytest.raises(MXNetError, match="no published peaks"):
        chip.peaks("TPU v99")


def test_require_accelerator_refuses_cpu():
    with pytest.raises(MXNetError, match="needs an accelerator"):
        chip.require_accelerator("a measurement")
    assert chip.describe()["platform"] == "cpu"


def _smoke(capsys, *argv):
    import json
    import chip_smoke
    rc = chip_smoke.main(list(argv))
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(l) for l in lines]


def test_chip_smoke_refuses_without_a_tpu(capsys):
    """No option, no TPU: non-zero before any work, nothing on stdout."""
    rc, lines = _smoke(capsys)
    assert rc != 0 and lines == []


def test_chip_smoke_rehearsal_reports_the_device_truthfully(capsys):
    rc, lines = _smoke(capsys, "--rehearse", "--only", "lm_train")
    assert rc == 0
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}
    phase = [l for l in lines if l.get("phase") == "lm_train"][0]
    assert phase["ok"] and phase["losses"][-1] < phase["losses"][0]


def test_chip_smoke_fails_when_a_phase_is_broken(capsys):
    """A NaN loss in one phase: "ok": false on the last line, rc != 0."""
    rc, lines = _smoke(capsys, "--rehearse", "--only", "lm_train",
                       "--break-phase", "lm_train")
    assert rc != 0 and lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
