"""Where the persistent compilation cache goes
(``repro.launch.compile_cache``). Each case runs in a fresh interpreter:
JAX reads ``JAX_COMPILATION_CACHE_DIR`` once, when it is imported."""
import os
import pathlib
import subprocess
import sys
import textwrap

_ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROG = textwrap.dedent("""
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    print(where)
    print(jax.config.jax_compilation_cache_dir)
    if {compile_once}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(4)).block_until_ready()
""")


def _run(env_dir, compile_once: bool):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    prog = _PROG.format(src=str(_ROOT / "src"), compile_once=compile_once)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-2:]


def test_env_dir_wins_and_receives_entries(tmp_path):
    where, configured = _run(tmp_path, compile_once=True)
    assert where == configured == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_default_is_fixed_repo_dir():
    where, configured = _run(None, compile_once=False)
    assert where == configured == str(_ROOT / ".jax_cache")
