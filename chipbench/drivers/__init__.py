"""Window drivers, one per kind of traffic; a traffic file names its
driver and gives its parameters (see ``chipbench.harness``)."""
