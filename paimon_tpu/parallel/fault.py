"""Fault classification + per-bucket retry policy for the maintenance
plane.

The mesh compaction engine (parallel/mesh_engine.py) treats a bucket as
its failure domain: a transient error anywhere in one bucket's window
stream — reading a sorted run, the device window kernel, writing or
rolling an output file — aborts and retries THAT bucket with capped
decorrelated-jitter backoff, and after `compaction.retry.max-attempts`
degrades it to the single-chip compact/manager.py path instead of
failing the whole job.  The degradation ladder is:

    mesh window stream  ->  retry (x max-attempts, jittered backoff)
                        ->  single-chip fallback (compaction.mesh.fallback)
                        ->  raise (bucket unrecoverable; job fails)

Only *transient* errors ride the ladder.  Programming errors
(ValueError, KeyError, schema bugs) and compile/lowering refusals of a
device program propagate immediately — retrying them would loop
deterministically and degrade silently.
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Optional

from paimon_tpu.options import CoreOptions

__all__ = ["is_transient_error", "BucketRetryPolicy"]

# error class NAMES treated as device/lane loss: jax surfaces device
# failures as jax.errors.JaxRuntimeError (a RuntimeError subclass we
# must not import at module scope — jax loads lazily everywhere else)
_DEVICE_ERROR_NAMES = frozenset({"JaxRuntimeError"})

# the same class also carries XLA/Mosaic compile and lowering refusals;
# its message leads with the status code.  These are what a program the
# compiler cannot build (or that cannot fit) reports — deterministic
# for a given program, so never transient
_COMPILE_STATUSES = ("INTERNAL", "UNIMPLEMENTED", "INVALID_ARGUMENT",
                     "RESOURCE_EXHAUSTED")


def is_transient_error(exc: BaseException) -> bool:
    """True when `exc` is worth retrying: a store-side 503
    (TransientStoreError), an IO fault (OSError covers InjectedIOError
    and FileNotFoundError from racing maintenance), or a device/lane
    loss (JaxRuntimeError whose status is not a compile refusal).

    DECODE errors are excluded even though they reach us as OSError
    (modern pyarrow raises plain OSError for torn footers / corrupt
    compressed pages): the format readers re-tag decode-phase failures
    as CorruptDataError — deterministic bad bytes, pointless to retry,
    and on the scan path they must stay eligible for the
    scan.ignore-corrupt-files skip.  ArrowException covers the
    ArrowInvalid flavors for completeness.
    """
    import pyarrow as pa

    from paimon_tpu.format.format import CorruptDataError
    from paimon_tpu.fs.object_store import TransientStoreError
    from paimon_tpu.utils.deadline import DeadlineExceededError

    if isinstance(exc, (CorruptDataError, pa.ArrowException)):
        return False
    if isinstance(exc, DeadlineExceededError):
        # the request's end-to-end budget is spent: retrying can only
        # waste a sick backend's capacity on a caller that is gone
        return False
    if isinstance(exc, (TransientStoreError, OSError)):
        return True
    if any(t.__name__ in _DEVICE_ERROR_NAMES
           for t in type(exc).__mro__):
        return not str(exc).lstrip().startswith(_COMPILE_STATUSES)
    return False


@dataclass
class BucketRetryPolicy:
    """`compaction.retry.*` + `compaction.mesh.fallback` in one bundle."""

    max_attempts: int = 3
    backoff_base_ms: float = 10.0
    fallback: bool = True
    rng: Optional[random.Random] = None

    @classmethod
    def from_options(cls, options: CoreOptions) -> "BucketRetryPolicy":
        return cls(
            max_attempts=options.get(
                CoreOptions.COMPACTION_RETRY_MAX_ATTEMPTS),
            backoff_base_ms=options.get(
                CoreOptions.COMPACTION_RETRY_BACKOFF),
            fallback=options.get(CoreOptions.COMPACTION_MESH_FALLBACK))

    def new_backoff(self):
        from paimon_tpu.utils.backoff import Backoff
        return Backoff(self.backoff_base_ms, rng=self.rng)

    def retry_call(self, fn, *, on_retry=None):
        """Run `fn` under this policy: transient errors retry with
        backoff up to max_attempts total attempts, then re-raise.
        Non-transient errors propagate immediately.  Each backoff
        sleep is a traced span (obs/trace.py) carrying the attempt
        number and error class, so retry storms are visible on the
        timeline instead of reading as unexplained gaps."""
        backoff = self.new_backoff()
        attempt = 0
        while True:
            attempt += 1
            try:
                return fn()
            except BaseException as e:      # noqa: BLE001
                if not is_transient_error(e) or \
                        attempt >= max(1, self.max_attempts):
                    raise
                if on_retry is not None:
                    on_retry(attempt, e)
                from paimon_tpu.obs.flight import EV_RETRY, record
                record(EV_RETRY, attempt=attempt,
                       error=type(e).__name__)
                from paimon_tpu.obs.trace import span
                with span("retry.backoff", cat="compaction",
                          attempt=attempt, error=type(e).__name__):
                    backoff.pause()
