"""Pallas kernels traced as if written nowhere.

A kernel's compiled module carries the source location of each of its
operations, every calling frame included, and the module's bytes are in the
key of the persistent compilation cache: with them a checkout at another
path, or a line moved in any caller, compiles every program that holds the
kernel again (PERF.md section 6, PR 41).  A kernel file calls
``exclude(__file__)`` once, so that its own frames are not "user code" to
JAX, and makes its ``pallas_call`` under ``nowhere()``, which gives the
kernel's operations a traceback that holds no other frames either.

``kernels_compiled()`` is the one answer to "is this a backend the kernels
are compiled for?": every kernel's ``_resolve`` and every chooser asks it.
"""

from __future__ import annotations

import functools
import threading

import jax
from jax._src import source_info_util
from jax._src.lib import xla_client

exclude = source_info_util.register_exclusion
exclude(__file__)


@functools.cache
def _traceback():
    """A traceback of a thread that ran nothing but a line of this file."""
    box = []
    thread = threading.Thread(
        target=lambda: box.append(xla_client.Traceback.get_traceback()))
    thread.start()
    thread.join()
    return box[0]


def nowhere():
    """The context a kernel file makes its ``pallas_call`` under."""
    return source_info_util.user_context(_traceback())


def kernels_compiled() -> bool:
    """Whether programs are being made for a backend the kernels are
    compiled for: anything but the CPU.  The interpreter is for the CPU,
    where the tests run; on any other backend a kernel that does not compile
    is an error, not a slower run.  Asked of JAX at every call, never at
    import: a tool describes another backend for the length of a run."""
    return jax.default_backend() != "cpu"
