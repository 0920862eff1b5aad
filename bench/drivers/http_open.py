"""Driver ``http_open``: an open loop of raw-binary ``:predict`` requests
over HTTP (`bench/serve.py`, `bench/loadgen.py`).

Traffic keys: ``arrival`` ("poisson"), ``rate_per_s``,
``images_per_request``, ``image_pool`` distinct images cycled through,
``connections`` the generator keeps open, the deployment
(``batch_size``, ``max_delay_ms``, ``max_queue_depth``), the served
model's training set (``n_train``, ``fit_batch``), ``trace_seconds``.

End-to-end (`serve.window`): ``predict_p99_ms``, every request timed
from when it was due, and ``predict_images_per_s``.
"""

from bench import serve

setup = serve.setup
window = serve.window
release = serve.release
check = serve.check
