"""Persistent XLA compile-cache placement: ``$JAX_COMPILATION_CACHE_DIR``
wins, otherwise one fixed path inside the checkout, and a cache that cannot
be set up raises. Plus the cosmetic AOT-warning filter (ISSUE 9): the
known-harmless
``+prefer-no-gather``/``+prefer-no-scatter`` mismatch is silenced at the
logging layer, while any genuine ISA mismatch still warns."""

import logging
import os
from unittest import mock

import jax
import pytest

from gordo_tpu.util import xla_cache
from gordo_tpu.util.xla_cache import (
    CosmeticAotMismatchFilter,
    host_fingerprint,
    install_aot_warning_filter,
    is_cosmetic_aot_mismatch,
    setup_persistent_xla_cache,
)


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    prior = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prior)


def _a_jax_module_logger() -> str:
    """Name of one of jax's own per-module loggers (where the AOT loader
    warning surfaces), whichever modules this jax version has."""
    return next(
        name for name in sorted(logging.root.manager.loggerDict)
        if name.startswith("jax.") and "compil" in name
    )


def test_fingerprint_stable_and_short():
    a, b = host_fingerprint(), host_fingerprint()
    assert a == b
    assert len(a) == 12
    int(a, 16)  # hex


def test_default_cache_dir_is_fixed_inside_the_checkout():
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    # nothing that varies between runs or hosts may be part of the path
    for platforms in ("cpu", "tpu", None):
        env = {} if platforms is None else {"JAX_PLATFORMS": platforms}
        with mock.patch.dict(os.environ, env, clear=False):
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
            if platforms is None:
                os.environ.pop("JAX_PLATFORMS", None)
            cache_dir = setup_persistent_xla_cache()
        assert cache_dir == os.path.join(repo_root, ".jax_cache")
    assert cache_dir == xla_cache.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert os.path.isdir(cache_dir)
    # the default lives in the checkout, so git must ignore it
    with open(os.path.join(repo_root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_explicit_env_dir_wins(tmp_path):
    explicit = str(tmp_path / "explicit-cache")
    with mock.patch.dict(
        os.environ, {"JAX_COMPILATION_CACHE_DIR": explicit}
    ):
        assert setup_persistent_xla_cache() == explicit
    assert jax.config.jax_compilation_cache_dir == explicit


def test_setup_failure_raises(tmp_path):
    # a cache dir that cannot exist (its parent is a file) must raise, not
    # leave the process quietly compiling everything again
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    with mock.patch.dict(
        os.environ, {"JAX_COMPILATION_CACHE_DIR": str(blocker / "cache")}
    ):
        with pytest.raises(OSError):
            setup_persistent_xla_cache()


def test_every_accelerator_compile_is_kept(monkeypatch):
    """On an accelerator every compile is cached whatever it cost, so a
    second run of the same programs adds no entries; on CPU jax's own
    threshold stays (see setup_persistent_xla_cache for why)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        setup_persistent_xla_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        setup_persistent_xla_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prior
        )


# ------------------------------------------- cosmetic AOT-warning filter
_COSMETIC_MSG = (
    "The loaded executable was compiled with CPU features "
    "'+avx2,+fma,+prefer-no-gather,+prefer-no-scatter' but the host "
    "supports '+avx2,+fma'; this discrepancy could lead to execution "
    "errors such as SIGILL."
)
_GENUINE_MSG = (
    "The loaded executable was compiled with CPU features "
    "'+avx2,+avx512f,+prefer-no-gather' but the host supports "
    "'+avx2,+prefer-no-gather'; this discrepancy could lead to execution "
    "errors such as SIGILL."
)


def _warning_record(message: str) -> logging.LogRecord:
    return logging.LogRecord(
        _a_jax_module_logger(), logging.WARNING, __file__, 1, message, None,
        None,
    )


def test_cosmetic_mismatch_detected():
    assert is_cosmetic_aot_mismatch(_COSMETIC_MSG)


def test_genuine_isa_mismatch_stays_loud():
    # one differing feature is real (avx512f): must NOT be classified
    # cosmetic even though a cosmetic pseudo-feature appears in both lists
    assert not is_cosmetic_aot_mismatch(_GENUINE_MSG)
    assert CosmeticAotMismatchFilter().filter(_warning_record(_GENUINE_MSG))


def test_filter_drops_only_the_cosmetic_warning():
    flt = CosmeticAotMismatchFilter()
    assert not flt.filter(_warning_record(_COSMETIC_MSG))
    assert flt.filter(_warning_record("unrelated warning about SIGILL"))
    assert flt.filter(_warning_record("ordinary log line"))


def test_unparseable_feature_lists_stay_loud():
    # parse failure must never silence: no quoted feature lists here
    message = "execution errors such as SIGILL may occur"
    assert not is_cosmetic_aot_mismatch(message)


def test_identical_feature_lists_not_classified_cosmetic():
    # empty symmetric diff means this is not the mismatch warning shape
    message = (
        "features '+avx2,+prefer-no-gather' vs '+avx2,+prefer-no-gather' "
        "could lead to execution errors such as SIGILL"
    )
    assert not is_cosmetic_aot_mismatch(message)


def test_install_is_idempotent_and_attached():
    install_aot_warning_filter()
    install_aot_warning_filter()
    jax_logger = logging.getLogger(_a_jax_module_logger())
    cosmetic_filters = [
        f for f in jax_logger.filters
        if isinstance(f, CosmeticAotMismatchFilter)
    ]
    assert len(cosmetic_filters) == 1


def test_setup_installs_the_filter():
    with mock.patch.dict(os.environ, {}, clear=False):
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        setup_persistent_xla_cache()
    assert any(
        isinstance(f, CosmeticAotMismatchFilter)
        for f in logging.getLogger("jax").filters
    )
