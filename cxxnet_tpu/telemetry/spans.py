"""Names of the host spans the program writes into a `jax.profiler`
trace, on the device trace's own clock.

Each is a `jax.profiler.TraceAnnotation` at a dispatch site that the
flight recorder (flight.py) also records, under the same words: a flight
entry of `kind` "train" / "eval" / "serve" and the span of that name
describe one dispatch, and a train entry carries the step number the
`train` span carries. While no trace runs an annotation is a no-op of
about a microsecond, and that is the whole arming rule: a trace started
by `profile_dir=`, `jax.profiler.trace()` or the benchmark's `--trace 1`
holds them, nothing else does. Inside the traced program the names are
scopes, not spans: docs/OBSERVABILITY.md "Reading a device trace".
"""

TRAIN = "train"                # StepTraceAnnotation, step_num = the step
TRAIN_STAGE = "train.stage"    # stage_batch / stage_chunk of unstaged input
TRAIN_KEY = "train.key"        # the step's RNG key, made with eager ops
TRAIN_CALL = "train.call"      # the jitted step's call alone
TRAIN_GUARD = "train.guard"    # read-back of the finite flag (check_nan)
EVAL_STEP = "eval.step"        # one evaluation dispatch
IO_WAIT = "io.wait"            # StagedPrefetcher.next blocked on its queue
SERVE_BATCH = "serve.batch"    # one Server dispatch; argument: bucket
