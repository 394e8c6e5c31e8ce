"""Small shared helpers (reference: kart/utils.py)."""

import contextlib
import functools
import itertools
import os


def chunked(iterable, size):
    """Yield successive lists of up to `size` items from `iterable`."""
    it = iter(iterable)
    while True:
        block = list(itertools.islice(it, size))
        if not block:
            return
        yield block


def materialised(generator_fn_or_type):
    """Decorator: call the generator function and materialise it into the given
    container type (default list). Usage:

        @materialised          # -> list
        @materialised(dict)    # -> dict
    """
    if isinstance(generator_fn_or_type, type):
        container = generator_fn_or_type

        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                return container(fn(*args, **kwargs))

            return wrapper

        return deco

    fn = generator_fn_or_type

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return list(fn(*args, **kwargs))

    return wrapper


def classproperty(fn):
    class _ClassProperty:
        def __init__(self, getter):
            self.getter = getter

        def __get__(self, obj, owner):
            return self.getter(owner)

    return _ClassProperty(fn)


@contextlib.contextmanager
def paused_gc():
    """Pause the cyclic garbage collector across a bulk-allocation section
    (restoring the caller's state). Refcounting still frees everything
    promptly; what this avoids is collector passes over millions of fresh,
    acyclic allocations — measured 2.3x on 1M-conflict materialisation and
    ~8% on bulk import."""
    import gc

    was_enabled = gc.isenabled()
    if was_enabled:
        gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


def pool_workers():
    """Threads for a pool of GIL-free native calls: as many as the host has
    cores, four at most. Read off the machine: no setting, no argument."""
    return max(1, min(os.cpu_count() or 1, 4))


def map_in_order(fn, items, workers, thread_name_prefix):
    """``fn(item)`` for each item on a pool of ``workers`` threads, the
    results yielded in item order. The next item is submitted when the
    consumer comes back for more, so at most ``workers + 1`` results exist
    at a time (the one being consumed among them) however far the pool
    could run ahead. An exception of ``fn`` is raised where its result
    would have been yielded; a consumer that stops early (``close()``)
    cancels what has not started."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    items = iter(items)
    pool = ThreadPoolExecutor(workers, thread_name_prefix=thread_name_prefix)
    try:
        in_flight = deque(
            pool.submit(fn, item) for item in itertools.islice(items, workers + 1)
        )
        while in_flight:
            yield in_flight.popleft().result()
            for item in itertools.islice(items, 1):
                in_flight.append(pool.submit(fn, item))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
