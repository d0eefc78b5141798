"""attention_kernel_share: the share (%) of the local step's attention time
(``attention_ms``) spent under the ``attention_kernel`` scope, where
``models/attention._attention`` calls the Pallas flash kernel; 0 where every
attention runs the jnp scan.  Reads nothing where ``attention_ms`` does not."""

import os

from bench.spec import load_module

_attention = load_module(os.path.join(os.path.dirname(__file__),
                                      "attention_ms.py"))


def read(t):
    m = _attention.measure(t)
    if not m or not m["attention"]:
        return None
    return 100.0 * m["attention_kernel"] / m["attention"]
