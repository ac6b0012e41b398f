"""Shared by every test: keep a test process under the kernel's limit on
memory maps.

Each XLA:CPU executable that JAX keeps in its caches holds memory maps,
and a process may hold at most ``vm.max_map_count`` of them (65530 by
default). A test process that compiles many programs, as the serving
tests do, crosses that limit, and the next compile's mmap fails inside
XLA as a segmentation fault that takes the whole worker down. After a
test that leaves the process past half the limit, JAX's caches are
cleared, which frees their executables' maps; later tests compile what
they need again."""
import gc
from pathlib import Path

import jax
import pytest


def _max_maps():
    try:
        return int(Path("/proc/sys/vm/max_map_count").read_text())
    except (OSError, ValueError):
        return None  # not Linux: no such limit to keep under


_MAX_MAPS = _max_maps()


def _maps() -> int:
    with open("/proc/self/maps", "rb") as f:
        return sum(1 for _ in f)


@pytest.fixture(autouse=True)
def _bounded_maps():
    yield
    if _MAX_MAPS is not None and _maps() > _MAX_MAPS // 2:
        jax.clear_caches()
        gc.collect()
