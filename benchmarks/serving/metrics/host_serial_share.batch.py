"""Session step loop: share of the traced window the host spent outside `serve.device_wait` (%); closed-loop batch cells."""
from serving.spec import metric_reader

read = metric_reader("host_serial_share")
