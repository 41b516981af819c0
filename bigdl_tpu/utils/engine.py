"""Runtime engine (≙ utils/Engine.scala, ThreadPool.scala).

The reference Engine owns MKL thread pools, core affinity, and the
Spark-executor topology (nodeNumber x coreNumber).  On TPU the compute
threading belongs to XLA; what remains host-side is (a) the device/mesh
topology, (b) a worker pool for data pipelines, and (c) process-group
initialization for multi-host pods (jax.distributed ≙ the Spark cluster
bootstrap).
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import jax

_state = threading.local()
_engine_lock = threading.Lock()
_initialized = False
_io_pool: Optional[ThreadPoolExecutor] = None
_core_number = os.cpu_count() or 1
_node_number = 1


def init(node_number: Optional[int] = None,
         core_number: Optional[int] = None,
         coordinator_address: Optional[str] = None,
         process_id: Optional[int] = None) -> None:
    """≙ Engine.init: single call to set up the runtime.  For multi-host
    pods pass coordinator_address/process_id to bootstrap jax.distributed
    (the Spark master/executor handshake analogue)."""
    global _initialized, _core_number, _node_number, _io_pool
    with _engine_lock:
        if coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=node_number or 1,
                process_id=process_id or 0)
        _node_number = node_number or jax.process_count()
        _core_number = core_number or os.cpu_count() or 1
        _io_pool = ThreadPoolExecutor(
            max_workers=max(2, _core_number // 2),
            thread_name_prefix="bigdl-io")
        _initialized = True


def is_initialized() -> bool:
    return _initialized


_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache(default_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache and return its
    directory — the one place in the repo that may name one.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this sets NO directory in code (whoever runs the program places the
    cache).  Otherwise the cache goes to ``default_dir`` or, failing
    that, ``<checkout>/.jax_cache``, as a normalised absolute path: the
    path is part of every cache key, so two spellings of one directory
    never hit each other.  Every program is cached, however fast it
    compiled."""
    path = os.environ.get(_CACHE_ENV)
    if not path:
        path = os.path.abspath(default_dir
                               or os.path.join(_CHECKOUT, ".jax_cache"))
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def core_number() -> int:
    """≙ Engine.coreNumber (host cores for data workers)."""
    return _core_number


def node_number() -> int:
    """≙ Engine.nodeNumber (processes in the pod)."""
    return _node_number


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def default_pool() -> ThreadPoolExecutor:
    """≙ Engine.default thread pool — host-side IO/augmentation workers."""
    global _io_pool
    if _io_pool is None:
        init()
    return _io_pool


def invoke(tasks) -> List:
    """Run callables on the worker pool and wait (≙ ThreadPool.invokeAndWait)."""
    pool = default_pool()
    return [f.result() for f in [pool.submit(t) for t in tasks]]
