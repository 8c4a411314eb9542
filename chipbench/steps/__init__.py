"""Found by name through chipbench.registry: how the check drives a
family's own step (``check``'s docstring has the contract)."""
