"""What the benchmark adds to an entry's trainer class, and nothing more:
per-step losses kept where the trainer already has them on the host."""

from __future__ import annotations


class QuietLogger:
    """The trainer prints through ``logger.log``; a run's stdout ends in the
    result line, so the loop's chatter is dropped."""

    def log(self, msg, log_type="info"):
        if log_type in ("warning", "error"):
            import sys

            print(f"trainer {log_type}: {msg}", file=sys.stderr)


class StepLosses:
    """Mixin: remembers every step's loss. ``_aggregate_epoch_metrics`` is
    where ``train_epoch`` hands over the epoch's per-step metrics after its
    one host transfer; nothing is added to the loop."""

    def _aggregate_epoch_metrics(self, host, synced=0):
        self.__dict__.setdefault("step_losses", []).extend(float(m["loss"]) for m in host)
        return super()._aggregate_epoch_metrics(host, synced)


def trainer_kwargs(cfg: dict, traffic: dict, *, seed, mesh, telemetry, save_folder) -> dict:
    """Constructor arguments every cell shares: validation and saves off, a
    fixed long schedule (the LR schedule is traced into the step, so it must
    not vary with --seconds)."""
    kw = {}
    if cfg["precision"]["compute"] == "float32":
        # Stated float32 (the CPU test presets): ask for it. The bfloat16 cells
        # pass nothing and get the entries' default program.
        kw["precision"] = "fp32"
    return dict(
        **kw,
        max_epoch=cfg["optimizer"]["schedule"]["total_epochs"],
        batch_size=traffic["global_batch"],
        chain_steps=traffic["chain_steps"],
        log_every=traffic["log_every"],
        mesh=mesh,
        seed=seed,
        telemetry=telemetry,
        have_validate=False,
        save_period=None,
        save_folder=save_folder,
        snapshot_path=None,
        progress=False,
        logger=QuietLogger(),
    )
