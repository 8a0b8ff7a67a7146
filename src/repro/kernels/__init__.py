# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    ``None`` chooses from the JAX backend: interpret on the CPU, which
    has no Pallas compiler, and compile (Mosaic) everywhere else.  An
    explicit ``True``/``False`` is returned unchanged, so ``False``
    always compiles.  Every kernel wrapper and every executor takes its
    default from here.
    """
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() == "cpu"
