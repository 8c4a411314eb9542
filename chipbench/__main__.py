from chipbench import harness  # first: it stamps the start of the process

harness.main()
