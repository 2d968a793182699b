"""Where JAX's persistent compilation cache lands
(``repro.launch.compile_cache``).  Each case runs in a fresh interpreter:
the cache directory is process-wide JAX state."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_PROBE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print("DIR", enable_compile_cache())
    print("CFG", jax.config.jax_compilation_cache_dir)
    if "--compile" in sys.argv:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    if "--scope" in sys.argv:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        scope = sys.argv[sys.argv.index("--scope") + 1]

        def f(x):
            with jax.named_scope(scope):
                return jnp.sort(x * 3 + 1)
        text = jax.jit(f).lower(jnp.arange(7.0)).compile().as_text()
        print("SCOPES", " ".join(sorted(
            {n for n in ("first", "second") if f"/{n}/" in text})))
""")


def _probe(env_dir, *flags):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, *flags], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_cache_goes_to_the_variable_when_set(tmp_path):
    where = tmp_path / "jax-cache"
    got = _probe(where, "--compile")
    assert got == {"DIR": str(where), "CFG": str(where)}
    assert any(where.iterdir()), "the compile left no entry in the cache"


def test_cache_defaults_to_the_repo_checkout():
    want = str(REPO / ".jax_cache")
    assert _probe(None) == {"DIR": want, "CFG": want}


def test_cached_program_keeps_its_own_name_stack(tmp_path):
    """Two programs that differ only in a named scope do not share an
    entry: the second compiles its own, and its operations carry its own
    scope (a trace of it names them by the code that ran)."""
    where = tmp_path / "jax-cache"
    assert _probe(where, "--scope", "first")["SCOPES"] == "first"
    assert _probe(where, "--scope", "second")["SCOPES"] == "second"
