"""Hand-compiled models of the Pulsar specs (``registry.COMPILED``)."""


class ByConstants:
    """Value identity for a compiled model: two models are equal, and
    hash equal, when they are of one class and bind equal constants
    (``self.c``, a frozen dataclass).

    A model is a static argument of the traced units of
    ``engine/bodies.py``: everything its kernels read is a function of
    its class and its constants, so two ``cli check``s of one binding
    present JAX's tracing cache with equal arguments and the second
    traces nothing, while any changed constant is a different key."""

    def __eq__(self, other):
        return type(other) is type(self) and other.c == self.c

    def __hash__(self):
        return hash((type(self), self.c))
