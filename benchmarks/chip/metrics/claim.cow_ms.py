"""Copy-on-write inside one claim: the summed wall time of the ``wf.cow``
spans under each outermost ``wf.claim`` span (the claim copying columns a
steering snapshot froze), averaged over the claims the tracer recorded."""
import progspans


def reduce(run):
    return progspans.claim_cow_ms(progspans.spans(run))
