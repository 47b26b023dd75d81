"""Share of the traced window's device self time that carries none of the
program's names: operations whose name stack holds no declared phase, no
function-call layer and no module below the model (`scope_reduce.scope_of`
gives them ""). What is left once the program names its work is what XLA
itself made without a name (copies, layout changes, the `%reverse` it
splits off a gather) and the few scalar operations of the step's own body.
None where the trace holds none of the declared phases at all: a program
without scopes, or a stale executable."""

from chipbench import scope_reduce


def read(facts: dict):
    return scope_reduce.read_share(facts, [scope_reduce.UNNAMED])
