"""``run_fused``: one experiment as the program's segmented ``lax.scan``
driver, all islands batched on the default device (one chip)."""


def make(problem, ea, mig, cfg, epochs, devices):
    from repro.core import run_fused

    return lambda key: run_fused(problem, ea, mig, n_islands=cfg["islands"],
                                 max_epochs=epochs, rng=key)
