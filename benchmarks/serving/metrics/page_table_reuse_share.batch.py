"""Paged state: share of the live rows whose page-table row `serve.begin_step` reused rather than built from the pool (%); closed-loop batch cells."""
from serving.spec import metric_reader

read = metric_reader("page_table_reuse_share")
