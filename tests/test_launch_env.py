"""The pre-jax environment layer's compile-cache rule: an externally set
``JAX_COMPILATION_CACHE_DIR`` is kept; otherwise the cache goes to one
fixed directory inside the checkout."""

import os
import warnings

import pytest

from repro.launch import env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _apply():
    with warnings.catch_warnings():
        # jax is already imported in the test process; the layer warns
        warnings.simplefilter("ignore", RuntimeWarning)
        return env.apply_from_environ()


def test_cache_dir_set_outside_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv(env.CACHE_ENV_VAR, str(tmp_path))
    written = _apply()
    assert os.environ[env.CACHE_ENV_VAR] == str(tmp_path)
    assert env.CACHE_ENV_VAR not in written


@pytest.mark.parametrize("unset", ["absent", "empty"])
def test_cache_dir_unset_goes_to_the_checkout(monkeypatch, unset):
    monkeypatch.setenv(env.CACHE_ENV_VAR, "")   # restored after the test
    if unset == "absent":
        monkeypatch.delenv(env.CACHE_ENV_VAR)
    written = _apply()
    expected = os.path.join(ROOT, ".jax_cache")
    assert env.DEFAULT_CACHE_DIR == expected
    assert os.environ[env.CACHE_ENV_VAR] == expected
    assert written[env.CACHE_ENV_VAR] == expected
    # fixed: a second application picks the same directory
    assert _apply().get(env.CACHE_ENV_VAR, expected) == expected
