# Run output (traces, unpacked checkouts) lives under out/; it holds no tests.
collect_ignore_glob = ["out/*"]
