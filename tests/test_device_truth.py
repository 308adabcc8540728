"""Nothing on the main paths hides the device: an explicit request for
a TPU tells the truth, one platform name, one peaks table, a compile
cache that is placeable from outside, and a visible DataLoader
transport."""

import os

import numpy as np
import pytest

import jax

import paddle_tpu as paddle


# -- places -----------------------------------------------------------------
@pytest.mark.parametrize("request_tpu", [
    lambda: paddle.set_device("tpu"),
    lambda: paddle.set_device("tpu:0"),
    lambda: paddle.to_tensor(np.ones(2, "float32")).to("tpu"),
    lambda: paddle.to_tensor(np.ones(2, "float32")).tpu(),
    lambda: paddle.to_tensor(np.ones(2, "float32"),
                             place=paddle.TPUPlace(0)),
    lambda: paddle.TPUPlace(3).jax_device(),
    lambda: paddle.CustomPlace("npu", 0).jax_device(),
])
def test_request_for_an_absent_device_raises(request_tpu):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="no '(tpu|npu)' devices"):
        request_tpu()
    assert paddle.get_device() == before     # a failed set_device sticks not


def test_out_of_range_device_id_raises_instead_of_clamping():
    n = len(jax.devices("cpu"))
    from paddle_tpu.framework.place import Place

    class Cpu(Place):
        device_type = "cpu"
    assert Cpu(n - 1).jax_device() == jax.devices("cpu")[n - 1]
    with pytest.raises(RuntimeError, match="out of range"):
        Cpu(n).jax_device()
    with pytest.raises(RuntimeError, match="out of range"):
        paddle.CUDAPlace(n).jax_device()


def test_cuda_aliases_resolve_to_the_default_accelerator():
    x = paddle.to_tensor(np.ones((2, 2), "float32"))
    assert paddle.CUDAPlace(0).jax_device() == jax.devices()[0]
    assert list(x.cuda()._data.devices()) == [jax.devices()[0]]
    assert list(x.cpu()._data.devices()) == [jax.devices("cpu")[0]]


# -- one platform name ------------------------------------------------------
def test_pallas_interpret_decides_from_flag_and_backend(monkeypatch):
    from paddle_tpu.flags import set_flags
    from paddle_tpu.ops.pallas import _common
    assert _common.interpret() is True                  # cpu backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _common.interpret() is False
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        assert _common.interpret() is True
    finally:
        set_flags({"FLAGS_pallas_interpret": False})
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'tpu' and 'cpu'"):
        _common.interpret()


# -- one peaks table --------------------------------------------------------
def test_chip_peaks_table_and_unknown_device_raises():
    from paddle_tpu.device.peaks import CHIP_PEAKS, chip_peaks
    from paddle_tpu.models.serving_engine import _chip_flops_default

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind
    assert chip_peaks(Dev("TPU v5 lite")) == (197e12, 819e9)
    assert "cpu" in CHIP_PEAKS                  # explicit, not a default
    assert _chip_flops_default() == chip_peaks().flops \
        == CHIP_PEAKS["cpu"].flops
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks(Dev("TPU v9 imaginary"))


# -- compile cache ----------------------------------------------------------
def test_compile_cache_follows_env_else_checkout(monkeypatch):
    from paddle_tpu.framework import compile_cache as cc
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cc.enable_compile_cache() == "/somewhere/else"
    assert updates == []                        # jax reads the env itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(repo, ".jax_cache")
    assert cc.enable_compile_cache() == want == cc.compile_cache_dir()
    assert updates == [("jax_compilation_cache_dir", want)]


def test_compile_cache_stays_off_under_test():
    assert jax.config.jax_enable_compilation_cache is False


# -- DataLoader transport ---------------------------------------------------
class _Rows:
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.full((4,), i, np.int64)


@pytest.mark.parametrize("kw,want", [
    (dict(num_workers=0), "inline"),
    (dict(num_workers=2, use_shared_memory=False), "threads"),
    (dict(num_workers=2, use_shared_memory=True), "shm"),
])
def test_dataloader_reports_its_transport(kw, want):
    from paddle_tpu.io import DataLoader
    loader = DataLoader(_Rows(), batch_size=4, **kw)
    assert loader.transport is None
    assert len(list(loader)) == 2
    assert loader.transport == want


def test_dataloader_transport_shows_the_queue_fallback(monkeypatch):
    from paddle_tpu.io import DataLoader, shm
    monkeypatch.setattr(shm, "shm_available", lambda: False)
    loader = DataLoader(_Rows(), batch_size=4, num_workers=2,
                        use_shared_memory=True)
    assert len(list(loader)) == 2
    assert loader.transport == "queue"          # fell back, and says so


# -- spawned replicas -------------------------------------------------------
def test_spawned_agent_takes_its_platform_from_the_spec(monkeypatch):
    from paddle_tpu.fleet import remote
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    class Q:
        def put(self, item):
            raise SystemExit(item)              # stop after the report

    class FakeAgent:
        _stop = True

        def __init__(self, *a, **kw):
            pass

        def start(self):
            return 1234
    monkeypatch.setattr(remote, "ReplicaAgent", FakeAgent)
    for spec, want in (({"factory": "json:dumps"}, []),
                       ({"factory": "json:dumps", "jax_platforms": "cpu"},
                        [("jax_platforms", "cpu")])):
        seen.clear()
        with pytest.raises(SystemExit):
            remote._agent_proc_main(spec, Q())
        assert seen == want
        assert "JAX_PLATFORMS" not in os.environ   # never defaulted
