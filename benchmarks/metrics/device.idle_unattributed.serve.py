"""The serve cell's ``device.idle_unattributed.ph`` (same reader): the
share of the slice's device-idle seconds under no ``serve.*`` / ``ph.*``
/ ``qp.*`` span. Moves ``req_per_s``."""

import harness

read = harness.load_module("metrics", "device.idle_unattributed.ph").read
