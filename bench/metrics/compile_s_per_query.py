"""Backend compile seconds inside the window per query, from JAX's
``/jax/core/compile/backend_compile_duration`` monitoring events."""


def read(rec: dict):
    if not rec["queries"]:
        return None
    return rec["compile_s"] / rec["queries"]
