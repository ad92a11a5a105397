from repro.optim.adamw import AdamWState, adamw_init, adamw_update  # noqa: F401
