# in-repo developer tooling (jax-free): the regression gate (lint +
# exact counts) and the graft-lint static analysis suite.
# Package-shaped so ``python -m tools.lint`` works from the repo root.
