"""Shared test utilities."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config


def tiny(arch="tinyllama-1.1b", n_layers=4):
    return reduced_config(get_config(arch), n_layers=n_layers)


def rand_tokens(key, batch, seq, vocab):
    return jax.random.randint(key, (batch, seq), 0, vocab)


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with n CPU host devices; return stdout.
    Raises on nonzero exit. The child is pinned to the CPU: the test
    process may hold the accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout
