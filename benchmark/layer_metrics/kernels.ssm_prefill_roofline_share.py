"""The greater of the chunked scan's FLOP and byte floors as a share of
``ssm_scan_prefill``'s device time in the traced stretch. Floors are for
the rows the programs computed (a bucket's or a riding piece's rows: the
scan runs over padding too), in every state-space layer. Through the run's
family (``benchmark/families/<runner>.py ssm_prefill_roofline_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "ssm_prefill_roofline_share")
