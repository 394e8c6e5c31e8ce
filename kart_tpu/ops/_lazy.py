"""Deferred jax loading.

``import jax`` costs ~1.8s of pure import time — paid by every CLI
invocation even when the numpy twin handles the whole command (small repos,
wedged accelerators). Kernels defined with :func:`lazy_jit` keep jax out of
module import; the real ``jax.jit`` happens on the first *call*.
"""


class _LazyJit:
    __slots__ = ("_fn", "_options", "_jitted")

    def __init__(self, fn, options):
        self._fn = fn
        self._options = options
        self._jitted = None

    @property
    def __wrapped__(self):
        return self._fn

    def __call__(self, *args, **kwargs):
        if self._jitted is None:
            import jax

            # unconditional, matching the pre-lazy invariant: int64 feature
            # keys and the PAD_KEY sentinel corrupt silently under x32, and
            # an inherited JAX_ENABLE_X64=0 must not defeat that
            jax.config.update("jax_enable_x64", True)
            self._jitted = jax.jit(self._fn, **self._options)
        return self._jitted(*args, **kwargs)


def lazy_jit(fn, **options):
    """jax.jit that defers the jax import to the first call; ``options``
    are jax.jit's own (``static_argnames``)."""
    return _LazyJit(fn, options)
