"""CLI entrypoint — the reference's six ``main.py`` variants as one command
(``Balanced All-Reduce/main.py:17-99``).

Run flow parity: init distributed -> build model (Xavier init, broadcast) ->
loaders (probe + partition) -> train_global -> rank-0 test evaluation with
P/R/F1 -> the six plots -> teardown.  Topology and data mode select the
variant (the reference selects by directory).

Example::

    python -m learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
        --epochs_global 2 --epochs_local 2 --topology ring --data_mode disbalanced
"""

from __future__ import annotations

import logging
import sys


def train_main(argv) -> dict:
    """The training command: train -> rank-0 test evaluation -> the six
    plots.  Returns ``train_global``'s results (``main`` drops them;
    ``chip_smoke.py`` checks them)."""
    from .config import config_from_args
    cfg = config_from_args(argv)
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")

    import jax
    from . import viz
    from .driver import train_global
    from .eval import evaluate

    results = train_global(cfg)

    # rank-0 final test evaluation (ref main.py:61-62); the driver
    # materialized the variables residency-agnostically (a scatter-
    # resident state carries no sliceable params tree — ISSUE 11)
    if jax.process_index() == 0:
        variables = results["variables"]
        test = results["test"]
        evaluate(results["model"], variables, test.images, test.labels,
                 cfg.batch_size, rank=0)
        # the six plots (ref main.py:65-77); use the number of epochs
        # actually recorded (a resumed run only records the new ones)
        epochs_run = len(results["global_train_losses"])
        viz.write_all(results, epochs_run, cfg.epochs_local, cfg.out_dir)
    return results


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # `main.py serve ...` — continuous-batching inference off a
        # sharded checkpoint (ISSUE 7); the --serve_* flag group and
        # --checkpoint_dir configure it, the model itself comes from the
        # checkpoint's MANIFEST metadata
        from .serve.api import serve_main
        return serve_main(argv[1:])
    train_main(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
