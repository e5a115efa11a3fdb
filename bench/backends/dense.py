"""The program's ``DecodeBackend``: the whole cache and the weights on one
chip.

A backend is one file ``bench/backends/<name>.py``, chosen by the
configuration's ``serve.backend`` (``bench/plugins.py``). It defines
``build(cfg, scfg, params, conf, devices)``: the program's backend for
the ``ModelConfig``, the ``ServeConfig``, the weights (made on the default
device), the configuration file and the run's devices. The backend names
its programs ``decode_step`` and ``prefill_into_cache``, which the
device-trace readers key on.
"""


def build(cfg, scfg, params, conf, devices):
    from repro.serve.sharded_cache import DecodeBackend
    return DecodeBackend(cfg, scfg, params)
