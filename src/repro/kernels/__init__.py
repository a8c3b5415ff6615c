# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax


def resolve_interpret(interpret=None) -> bool:
    """Pallas interpret mode: an explicit value wins; the default runs the
    compiled kernel on a TPU and the interpreter everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
