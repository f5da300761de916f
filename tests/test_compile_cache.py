"""The one place the persistent compilation cache is put
(utils/compile_cache.py)."""

from pathlib import Path

import jax
import pytest

from go_crdt_playground_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_gives_one_fixed_path_in_the_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.place_compile_cache()
    assert compile_cache.place_compile_cache() == first
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_no_repo_file_lets_several_processes_load_libtpu():
    """A second process on the chip must fail loudly, not share it."""
    hits = []
    for path in REPO.rglob("*"):
        if ".git" in path.parts or not path.is_file() \
                or path.suffix not in (".py", ".sh", ".toml", ".cfg"):
            continue
        if "ALLOW_MULTIPLE_" + "LIBTPU_LOAD" in path.read_text(
                errors="ignore"):
            hits.append(str(path))
    assert not hits
