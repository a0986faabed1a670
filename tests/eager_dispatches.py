"""A counter of JAX's eager dispatches, for the tests that hold a served or a
designer-level suggest to the compiled programs it launches."""


class EagerDispatches:
    """Counts the calls of ``jax._src.dispatch.apply_primitive``: the one
    road of every eager operation. Primitives hold the function itself, so
    the count is taken where it looks up its per-primitive callable, once a
    call."""

    def __enter__(self):
        from jax._src import dispatch

        self._dispatch = dispatch
        self._lookup = dispatch.xla_primitive_callable
        self.count = 0

        def counting(prim, **params):
            self.count += 1
            return self._lookup(prim, **params)

        dispatch.xla_primitive_callable = counting
        return self

    def __exit__(self, *exc):
        self._dispatch.xla_primitive_callable = self._lookup
