"""Time one claim runs beside another thread's work: the outermost
``wf.claim`` span's wall time during which another thread was inside a
``wf.*`` span (the analyst's sweep, a shard's partial sweep or ship, the
shipper's encode, send or ack), averaged over the claims the tracer
recorded."""
import progspans


def reduce(run):
    return progspans.claim_overlap_ms(progspans.spans(run))
