"""Found by name through chipbench.registry."""
