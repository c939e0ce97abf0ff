"""Device self time of the operations under ``SemiJoin#n`` scopes
(``exec/executor.PlanInterpreter.run``: building the filter side's
membership table and probing it, not the filter side's own operators)
inside one statement of the class: median over the class's statements
wholly inside the traced sub-window; ms; closed loops only. One reader
for every ``<class>_semijoin_ms``: the harness hands it the class that
the metric's name holds."""

import opnames


def read(ctx, cls):
    return opnames.class_kind_ms(ctx, cls, ("SemiJoin",))
