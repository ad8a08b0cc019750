"""PR 21 bring-up contracts that need no chip: where the compile cache
lives, that a server says which device it got, and that start-up failures
(a bucket that cannot warm, a native build that does not compile) are errors
rather than log lines."""

import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from seldon_core_tpu import utils
from seldon_core_tpu.native import staging
from seldon_core_tpu.parallel.topology import Topology
from seldon_core_tpu.transport.ipc import (
    RING_FILE_BYTES,
    ModelExecutor,
    ring_geometry,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_set_means_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert utils.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_checkout_relative(monkeypatch, tmp_path):
    """Unset: <checkout>/.jax_cache — the same from another cwd and pid."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert utils.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from seldon_core_tpu.utils import configure_compile_cache as c\n"
         "print(c()); print(jax.config.jax_compilation_cache_dir)"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


def test_topology_says_which_device_it_got(caplog):
    with caplog.at_level(logging.INFO, "seldon_core_tpu.parallel.topology"):
        topo = Topology.detect()
    dev = jax.devices()[0]
    assert (topo.platform, topo.device_kind) == (dev.platform, dev.device_kind)
    for text in (repr(topo), caplog.text):
        assert f"platform={dev.platform}" in text
        assert f"device_kind={dev.device_kind!r}" in text


def test_warm_failure_is_fatal():
    class TooBig:
        batch_buckets = (1, 8)
        _config = {"input_shape": [4]}

        def predict(self, X, names, meta=None):
            if X.shape[0] > 1:
                raise MemoryError("bucket 8 does not fit")
            return np.zeros((X.shape[0], 2))

    with pytest.raises(MemoryError, match="bucket 8"):
        ModelExecutor([TooBig()]).warm()


def test_ring_slots_fit_the_largest_bucket_frame():
    """The 1 MiB default slot cannot carry ONE 224x224x3 image as f64."""
    class M:
        def __init__(self, shape, buckets):
            self._config, self.batch_buckets = {"input_shape": shape}, buckets

    assert ring_geometry([]) == (1024, 1 << 20)
    assert ring_geometry([M([4], (1, 1024))]) == (1024, 1 << 20)
    capacity, slot = ring_geometry([M([224, 224, 3], (1, 8, 64)), M([4], (1,))])
    assert 64 * 224 * 224 * 3 * 8 < slot < 65 * 224 * 224 * 3 * 8
    assert capacity == 2 and capacity * slot <= RING_FILE_BYTES
    capacity, slot = ring_geometry([M([224, 224, 3], (1,))])
    assert capacity == 128 and capacity * slot <= RING_FILE_BYTES
    # one frame larger than the whole budget still gets a working ring
    assert ring_geometry([M([224, 224, 3], (512,))])[0] == 2


def test_refused_ring_file_says_why(tmp_path):
    """A ring that cannot be created names the refusal (here RLIMIT_FSIZE,
    which counts a sparse file's length) instead of "could not create"."""
    import errno
    import resource

    if not staging.native_available():
        pytest.skip("no native toolchain")
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 20, hard))
    try:
        with pytest.raises(OSError, match="4 slots x 1048576 bytes") as e:
            staging.SharedRing(str(tmp_path / "r"), capacity=4,
                               slot_size=1 << 20, create=True)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    assert e.value.errno == errno.EFBIG and not list(tmp_path.iterdir())


def test_failed_native_build_raises_with_makes_stderr(monkeypatch, tmp_path):
    if staging.shutil.which("make") is None:
        pytest.skip("no make")
    (tmp_path / "Makefile").write_text(
        "all:\n\t@echo 'ring.cc:1: error: boom' >&2; exit 1\n")
    monkeypatch.setattr(staging, "_NATIVE_DIR", str(tmp_path))
    with pytest.raises(staging.NativeBuildError, match="ring.cc:1: error: boom"):
        staging.build_native()
