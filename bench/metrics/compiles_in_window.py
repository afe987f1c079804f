"""Programs JAX compiled, or loaded from its persistent cache, while the
window was open (its ``backend_compile`` events): each is a shape the
warm-up missed."""


def read(run):
    return run.compiles
