"""Where the persistent compile cache lives (utils/compile_cache.py): a
directory placed from outside is JAX's alone; otherwise one fixed path in
the checkout, started empty when its entries came from another host's CPU."""

import jax

from horovod_tpu.utils.compile_cache import ENV, configure_compile_cache


def test_cache_placed_from_outside_is_left_to_jax(monkeypatch, tmp_path):
    outside = str(tmp_path / "outside")
    monkeypatch.setenv(ENV, outside)
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache(str(tmp_path / "checkout")) == outside
    assert jax.config.jax_compilation_cache_dir == before   # nothing set in code
    assert not (tmp_path / "checkout").exists()
    assert not (tmp_path / "outside").exists()               # nor touched


def test_default_is_a_fixed_path_started_empty_on_a_foreign_host(
        monkeypatch, tmp_path):
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    cache = tmp_path / ".jax_cache"
    try:
        assert configure_compile_cache(str(tmp_path)) == str(cache)
        assert jax.config.jax_compilation_cache_dir == str(cache)
        entry = cache / "jit_step-abc-cache"
        entry.write_bytes(b"compiled here")
        configure_compile_cache(str(tmp_path))
        assert entry.exists()                    # same host: entries kept
        (cache / "host_cpu").write_text("another-hosts-cpu\n")
        configure_compile_cache(str(tmp_path))
        assert not entry.exists()                # foreign entries: start empty
        assert (cache / "host_cpu").read_text().strip() != "another-hosts-cpu"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
