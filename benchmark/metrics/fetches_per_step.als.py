"""fetches_per_step.als: program `<op>:fetch` spans (host fetches after a dispatch) that start in the traced window, per unit of work."""

from benchmark.span_metrics import fetches_per_step as read  # noqa: F401
