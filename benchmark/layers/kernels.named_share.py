"""Device self time of the operations under a scope the program gave
(``jax.named_scope("<Kind>#<n>")`` per plan node), over all device self
time of the traced sub-window; a share. What is outside is work no plan
operator asked for by name (result assembly, segment hand-over)."""

import opnames


def read(ctx):
    return opnames.named_share(ctx)
