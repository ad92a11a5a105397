"""Runtime layer (repro.runtime): seconds of ate_interval() over the
replicates it ran (TaskRuntime.map over replicate chunks, the memory
model's probes included), from the harness span."""


def read(run):
    s = run.span_seconds("bootstrap")
    reps = run.config["causal_config"]["n_bootstrap"] * len(s)
    return sum(s) / reps if s else None
