"""What every chip entry point does first: find the TPU (or refuse), and
keep JAX's persistent compilation cache where the program puts it."""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chip(chips: int):
    """The first TPU device, or None (with the reason on stderr) when JAX
    finds no TPU or fewer than ``chips``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"bench: JAX's default device is {dev.platform!r}, not a TPU; "
            f"the benchmark runs only on the chip")
        return None
    if len(devices) < chips:
        log(f"bench: {chips} chips needed, {len(devices)} visible")
        return None
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program goes to the persistent cache, however fast it compiled,
    # so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    return dev
