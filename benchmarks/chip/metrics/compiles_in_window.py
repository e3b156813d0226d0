"""Host driver + LB turn: programs compiled or loaded from the persistent
cache inside the window (JAX's ``backend_compile`` events), such as the
recompiles an adoption or an emigrant-pack resize asks for."""


def read(run):
    return run.compiles_in_window
