"""Share of the traced window's device self time spent before the model
sees a batch: the u8 finish (`finish_u8`) and the on-device augmentation
(`augment` with its stages `flip`, `crop_jitter`, `rand_ops`, `mix`), as
`chipbench/scopes.json` lists them under `prologue`. Elementwise work on
float32 rows; the model's own input cast is `cast_in` and not counted
here, and what XLA splits off without a name (the flip's `%reverse`) is in
`step_unnamed_pct`. None where the trace holds none of the declared
phases."""

from chipbench import scope_reduce


def read(facts: dict):
    return scope_reduce.read_share(facts, scope_reduce.declared()["prologue"])
