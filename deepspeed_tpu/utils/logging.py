"""Rank-filtered logging.

TPU-native analogue of the reference's ``deepspeed/utils/logging.py:16,49``
(``logger`` + ``log_dist``).  On JAX, "rank" means ``jax.process_index()`` —
one process per host rather than one per accelerator — so rank filtering is
per-host.  Inside SPMD computation there are no ranks at all; logging only
happens at the host level.
"""

import functools
import logging
import os
import sys

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


class _LoggerFactory:
    @staticmethod
    def create_logger(name=None, level=logging.INFO):
        if name is None:
            raise ValueError("name for logger cannot be None")
        formatter = logging.Formatter(
            "[%(asctime)s] [%(levelname)s] [%(filename)s:%(lineno)d:%(funcName)s] %(message)s")
        logger_ = logging.getLogger(name)
        logger_.setLevel(level)
        logger_.propagate = False
        if not logger_.handlers:
            ch = logging.StreamHandler(stream=sys.stdout)
            ch.setLevel(level)
            ch.setFormatter(formatter)
            logger_.addHandler(ch)
        return logger_


logger = _LoggerFactory.create_logger(
    name="deepspeed_tpu",
    level=LOG_LEVELS.get(os.environ.get("DSTPU_LOG_LEVEL", "info").lower(), logging.INFO))


def route_logs_to_stderr():
    """Point the package logger's stream handlers at stderr.

    For machine-readable stdout protocols — ``benchmark/run.py``'s final
    JSON line and ``python -m deepspeed_tpu.analysis --json`` — the
    engine's INFO chatter must never interleave with (or trail) the
    payload the driver parses off stdout.
    """
    for h in logger.handlers:
        if isinstance(h, logging.StreamHandler):
            try:
                h.setStream(sys.stderr)
            except ValueError:
                # setStream flushes the OLD stream first, which may
                # already be closed (a captured stream from a finished
                # pytest test); swap without the flush
                h.stream = sys.stderr


@functools.lru_cache(maxsize=None)
def _process_index():
    # Lazy: jax.process_index() is only valid after backend init; cache it.
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


def log_dist(message, ranks=None, level=logging.INFO):
    """Log ``message`` only on the listed process indices (``None``/[-1] = all).

    Parity: reference ``deepspeed/utils/logging.py:49 log_dist``.
    """
    my_rank = _process_index()
    if ranks is None or len(ranks) == 0 or -1 in ranks or my_rank in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def print_rank_0(message):
    if _process_index() == 0:
        logger.info(message)


def warning_once(message, _seen=set()):
    if message not in _seen:
        _seen.add(message)
        logger.warning(message)
