"""One module per kind of traffic; a traffic file names its driver.

    run(cell: Cell) -> {"correct", "attempted", "failed", "device",
                        "values": {end-to-end metric: value},
                        "evidence": {...what the layer readers read}}
"""
