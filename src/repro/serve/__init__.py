from repro.serve.decode import BatchedServer, Request, generate
from repro.serve.autotune import (AutotuneConfig, AutotuneReport,
                                  ServeAutotuner, bucket_key, snap_scale,
                                  split_bucket_key)
