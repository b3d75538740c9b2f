"""What is left of the reference's compat.py (compat.py:1-31).

The reference shimmed TF1/TF2 API drift.  This package is written for the
one installation it runs on (jax/jaxlib 0.9.0) and calls the current JAX
API where it is used — no version shims live here.
"""


def export_chief_only(save_fn, is_chief, *args, **kwargs):
    """Run a model-export function on the chief only (reference: compat.py:10-17).

    The reference had non-chief workers save to a throwaway local dir because
    MultiWorkerMirroredStrategy required symmetric saves; JAX has no such
    requirement, so non-chief is a no-op.
    """
    if is_chief:
        return save_fn(*args, **kwargs)
    return None
