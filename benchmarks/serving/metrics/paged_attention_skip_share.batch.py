"""Kernels: share of the batch's page-table slots that `paged_attention` does not copy (%); closed-loop batch cells."""
from serving.spec import metric_reader

read = metric_reader("paged_attention_skip_share")
