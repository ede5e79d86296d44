"""The benchmark's own tests run on the CPU at rehearsal sizes:
``python -m pytest benchmark/tests``.

Their graphs, traces and compiled programs go to a directory of the
test session, never to the checkout's ``benchmark/cache``, which the
chip runs use."""

import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"


@pytest.fixture(autouse=True, scope="session")
def _session_cache(tmp_path_factory):
    from benchmark import dataset

    cache = tmp_path_factory.mktemp("benchmark_cache")
    saved = dataset.CACHE, os.environ.get("JAX_COMPILATION_CACHE_DIR")
    dataset.CACHE = str(cache)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "xla")
    yield
    dataset.CACHE = saved[0]
    if saved[1] is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = saved[1]
